#!/usr/bin/env python
"""Kill -9 mid-run restart smoke: crash-consistency end to end.

Each drill is three subprocess runs of the serve driver (same
deterministic engine: seeded tokenizer corpus + PRNGKey(0) init):

  1. reference — fault-free run, ``--print-ids`` captures the greedy
     token ids per request;
  2. crash — same workload with ``--journal`` armed and
     ``--crash-after-syncs K``: the TokenJournal SIGKILLs the process
     (no atexit, no flush — a real crash) right after its K-th fsync,
     mid-decode;
  3. restore — ``--restore --journal``: replays the journal, resumes
     every live request from its validated committed prefix, and must
     print IDS lines bitwise-identical to the reference run.

The drill runs TWICE: once with the baseline workload, and once with
``--prefix-cache`` over a workload whose prompts repeat (a small page
size makes whole-page prefix hits certain), so restore exercises the
cache-warm path — restored admissions re-acquire shared pages through
the radix cache and adopt fork-point checker snapshots, and must STILL
be bitwise-identical to the (equally cache-enabled) reference.

The smoke fails if a crash run does NOT die by SIGKILL (workload too
small for K syncs), if restore errors, or if any row's ids differ.

Usage: python tools/restart_smoke.py [--device-loop] [--keep]
(repo root; needs PYTHONPATH=src semantics handled internally).
"""
import argparse
import os
import signal
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKLOAD = ["--smoke", "--grammar", "json", "--mode", "domino",
            "--prompts", "3", "--max-tokens", "16", "--slots", "2",
            "--seed", "0"]

# cache-warm drill: 5 prompts over the 4-entry base-prompt cycle, so at
# least one prompt repeats verbatim; page size 8 keeps whole pages well
# inside the short prompts (argparse takes the LAST occurrence, so these
# override the baseline workload's values)
WARM_EXTRA = ["--prompts", "5", "--page-size", "8", "--prefix-cache"]


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(REPO, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env


def _run(extra, check_rc=0):
    cmd = [sys.executable, "-m", "repro.launch.serve"] + WORKLOAD + extra
    print(f"[restart-smoke] $ {' '.join(cmd)}", flush=True)
    p = subprocess.run(cmd, cwd=REPO, env=_env(),
                       stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                       text=True)
    sys.stdout.write(p.stdout)
    if check_rc is not None and p.returncode != check_rc:
        raise SystemExit(f"[restart-smoke] FAIL: rc={p.returncode}, "
                         f"expected {check_rc}")
    return p


def _ids(out: str):
    rows = {}
    for ln in out.splitlines():
        if ln.startswith("IDS "):
            parts = ln.split()
            rows[int(parts[1])] = [int(t) for t in parts[2:]]
    return rows


def _drill(extra, crash_after_syncs, keep, label):
    """One reference -> crash -> restore cycle; returns restored rows."""
    ref = _run(extra + ["--print-ids"])
    want = _ids(ref.stdout)
    if not want or not any(want.values()):
        raise SystemExit(f"[restart-smoke] FAIL({label}): reference run "
                         f"produced no token ids")

    fd, journal = tempfile.mkstemp(prefix="restart_smoke_",
                                   suffix=".journal")
    os.close(fd)
    os.unlink(journal)                  # serve creates it fresh
    try:
        crash = _run(extra + ["--journal", journal, "--crash-after-syncs",
                              str(crash_after_syncs)],
                     check_rc=None)
        if crash.returncode != -signal.SIGKILL:
            raise SystemExit(
                f"[restart-smoke] FAIL({label}): crash run exited rc="
                f"{crash.returncode}, expected SIGKILL "
                f"(-{int(signal.SIGKILL)}) — workload finished before "
                f"{crash_after_syncs} journal syncs?")
        if not os.path.exists(journal) or not os.path.getsize(journal):
            raise SystemExit(f"[restart-smoke] FAIL({label}): crashed "
                             f"run left no journal bytes")

        rest = _run(extra + ["--restore", "--journal", journal,
                             "--print-ids"])
        got = _ids(rest.stdout)
        if got != want:
            for rid in sorted(set(want) | set(got)):
                a, b = want.get(rid), got.get(rid)
                mark = "ok" if a == b else "MISMATCH"
                print(f"[restart-smoke] rid {rid}: {mark}\n"
                      f"  reference: {a}\n  restored:  {b}")
            raise SystemExit(f"[restart-smoke] FAIL({label}): restored "
                             f"output is not bitwise-identical to the "
                             f"reference")
    finally:
        if keep:
            print(f"[restart-smoke] journal kept at {journal}")
        elif os.path.exists(journal):
            os.unlink(journal)
    print(f"[restart-smoke] {label}: SIGKILL after {crash_after_syncs} "
          f"syncs, {len(want)} request(s) restored bitwise-identical")
    return want


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--device-loop", action="store_true",
                    help="route certified rows through the fused "
                         "device loop in all runs")
    ap.add_argument("--crash-after-syncs", type=int, default=4)
    ap.add_argument("--keep", action="store_true",
                    help="keep the journal files for inspection")
    args = ap.parse_args()
    dev = ["--device-loop"] if args.device_loop else []

    base = _drill(dev, args.crash_after_syncs, args.keep, "base")
    warm = _drill(dev + WARM_EXTRA, args.crash_after_syncs, args.keep,
                  "prefix-cache")
    print(f"[restart-smoke] OK: base ({len(base)} requests) and "
          f"prefix-cache ({len(warm)} requests) drills both restored "
          f"bitwise-identical")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

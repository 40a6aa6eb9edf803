#!/usr/bin/env python
"""Hot-path lint: structural regressions the test suite can't catch.

The mask pipeline's whole point (PR 4) is that the serving tick never
materializes dense ``(B, V)`` data on the host: masks stay packed uint32
end to end and the fused kernel unpacks in-register.  Nothing functional
breaks if someone reintroduces a dense staging array or a
``bitmask.unpack`` call on the tick path — output is identical, only 8x
slower on the mask bytes — so tests stay green while the paper's headline
property quietly rots.  This linter fails CI instead.

Rules (AST-based, stdlib only):

  R1  no dense >=2-D array allocation (``np.zeros((B, V))``-style, or
      ``np.tile``) inside the scheduler's tick-path functions or the
      masked-sample dispatch module;
  R2  no ``unpack(...)`` calls in those same scopes (packed masks must
      reach the kernel packed);
  R3  no wall-clock/global-RNG nondeterminism in ``src/repro/core/``:
      ``time.time``/``datetime.now``/``datetime.utcnow``, module-level
      ``random.*`` draws, or ``np.random.*`` (``time.perf_counter`` /
      ``time.monotonic`` are fine — they feed timing *stats*, not
      decisions; per-request ``np.random.Generator`` objects are created
      outside core/ and passed in);
  R4  no swallowed exceptions in ``src/repro/serving/``: a bare
      ``except:`` or a handler whose body is only ``pass``/``...``
      hides a failure that the fault-tolerance layer (PR 7) must map to
      an explicit per-request terminal status (``internal_error``,
      ``rejected``, ...) — silent constraint-engine failures corrupt
      downstream results without a trace;
  R5  no file-sync calls (``fsync``/``flush``/``commit_tick``/``sync``)
      inside the tick-path functions: the crash journal (PR 9) buffers
      during tick phases and does ALL its file I/O in ``_journal_tick``
      at the tick boundary — an fsync on the per-token path serializes
      decode on disk latency, which is exactly the overhead the batched
      write-ahead design exists to avoid;
  R6  no radix-tree mutation or checker-state serialization inside
      tick-path functions: prefix-cache traffic
      (``self.prefix_cache.insert/lookup/put_checker/get_checker`` and
      checker ``snapshot()`` calls) belongs at admission/teardown
      boundaries (``_admit``/``_finish``/``_preempt``/``adopt``) — a
      tree walk or a hypothesis-set fork per token would put O(prefix)
      host work back on the per-token path the cache exists to shorten.
      Only ``evict()``/``evictable()`` may run from tick functions
      (``_ensure_pages`` reclaims cache-only pages under pool pressure);
  R7  no direct profiler call (``jax.profiler.*``, ``TraceAnnotation``,
      ``TraceMe``) inside tick-path functions: host spans go through
      ``span(...)`` (``serving/tracing.py``), which puts each in the
      ``serve.*`` namespace that trace readers select by — a bare
      annotation would name a phase no reader looks for.

A finding is suppressed by putting ``# hotpath-lint: allow`` on the
offending physical line (or the line above it).  Every suppression is a
reviewed, deliberate exception — the scheduler's sampled-row unpack is
the canonical one.

Usage: ``python tools/lint_hotpath.py`` (from the repo root; exits 1 on
violations).  Pass file paths to restrict the run.
"""
from __future__ import annotations

import ast
import os
import sys
from typing import List, Optional, Set, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRAGMA = "hotpath-lint: allow"

# scheduler functions on the per-token serving critical path (admission /
# teardown helpers deliberately excluded — they may allocate)
TICK_FUNCS: Set[str] = {
    "step", "_verify_width", "_reset_vacant_lens", "_checker_bits",
    "_prebuild_masks", "_choose", "_commit_first", "_run_decode",
    "_plain_step", "_spec_step", "_verify_row", "_fixup_refeed",
    "_ensure_pages", "_shrink_pages", "_sync_pages", "_reap",
    # device-resident fused loop (PR 8): the whole point is per-BLOCK
    # host sync, so its tick functions must not smuggle dense host
    # staging or unpacks back in (_build_fused is excluded — it runs
    # once, at trace time, not per tick)
    "_device_step", "_resync_row", "_sid_for", "_device_ready",
    "_advance_sid", "_audit_sid",
    # durability (PR 9): these run inside tick phases and may only
    # BUFFER journal records — _journal_tick (the designed tick-boundary
    # flush point) is deliberately NOT in this set
    "_journal_submit", "_journal_commit", "_deadline_cap",
}

ALLOC_FUNCS = {"zeros", "ones", "empty", "full", "tile"}
# R6: the only prefix-cache operations a tick function may invoke
# (allocation-pressure reclaim); everything else is boundary-only
PREFIX_CACHE_TICK_OK = {"evict", "evictable"}
# R5: journal/file-sync entry points banned from tick-path functions
SYNC_BANNED = {"fsync", "flush", "commit_tick", "sync"}
# R7: profiler entry points banned from tick-path functions
PROFILER_MODULES = {"jax.profiler", "profiler"}
PROFILER_CALLS = {"TraceAnnotation", "TraceMe"}
CLOCK_BANNED = {("time", "time"), ("datetime", "now"),
                ("datetime", "utcnow"), ("datetime", "today")}
RANDOM_FUNCS = {"random", "randint", "choice", "choices", "shuffle",
                "uniform", "seed", "randrange", "sample"}


class Finding:
    def __init__(self, path: str, line: int, rule: str, msg: str):
        self.path, self.line, self.rule, self.msg = path, line, rule, msg

    def __str__(self) -> str:
        rel = os.path.relpath(self.path, REPO)
        return f"{rel}:{self.line}: [{self.rule}] {self.msg}"


def _has_pragma(lines: List[str], lineno: int) -> bool:
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(lines) and PRAGMA in lines[ln - 1]:
            return True
    return False


def _call_name(node: ast.Call) -> Tuple[Optional[str], Optional[str]]:
    """('np', 'zeros') for np.zeros(...), (None, 'unpack') for unpack(...)."""
    f = node.func
    if isinstance(f, ast.Attribute):
        if isinstance(f.value, ast.Name):
            return f.value.id, f.attr
        if isinstance(f.value, ast.Attribute) \
                and isinstance(f.value.value, ast.Name):
            # e.g. np.random.randint -> ('np.random', 'randint')
            return f"{f.value.value.id}.{f.value.attr}", f.attr
        return None, f.attr
    if isinstance(f, ast.Name):
        return None, f.id
    return None, None


def _is_dense_shape(arg: ast.expr) -> bool:
    """Shape literal with >=2 dims (tuple/list of 2+ elements)."""
    return isinstance(arg, (ast.Tuple, ast.List)) and len(arg.elts) >= 2


def _check_hot_scope(tree_nodes, path: str, lines: List[str],
                     where: str) -> List[Finding]:
    out: List[Finding] = []
    for node in tree_nodes:
        if not isinstance(node, ast.Call):
            continue
        if _has_pragma(lines, node.lineno):
            continue
        base, name = _call_name(node)
        if name in ALLOC_FUNCS and base in ("np", "jnp", "numpy", "jax"):
            dense = (name == "tile"
                     and len(node.args) >= 2 and _is_dense_shape(node.args[1])
                     ) or (name != "tile" and node.args
                           and _is_dense_shape(node.args[0]))
            if dense:
                out.append(Finding(
                    path, node.lineno, "R1",
                    f"dense >=2-D allocation {base}.{name}(...) in "
                    f"{where} — the tick path must stay packed "
                    f"(ceil(V/32) uint32 words per row, reused buffers)"))
        if name == "unpack":
            out.append(Finding(
                path, node.lineno, "R2",
                f"unpack(...) call in {where} — packed masks must reach "
                f"the fused kernel packed; unpacking on the host "
                f"re-creates the dense (B, V) traffic PR 4 removed"))
        if name in SYNC_BANNED:
            out.append(Finding(
                path, node.lineno, "R5",
                f"file-sync call {name}(...) in {where} — journal I/O "
                f"must batch at the tick boundary (_journal_tick); an "
                f"fsync/flush on the per-token path serializes decode "
                f"on disk latency"))
        if (base == "self.prefix_cache"
                and name not in PREFIX_CACHE_TICK_OK) or name == "snapshot":
            out.append(Finding(
                path, node.lineno, "R6",
                f"prefix-cache/checker-state call {name}(...) in {where} "
                f"— radix-tree mutation and checker serialization belong "
                f"at admission/teardown boundaries (_admit/_finish/"
                f"_preempt/adopt), never on the per-token tick path; "
                f"only evict()/evictable() may run under pool pressure"))
        if base in PROFILER_MODULES or name in PROFILER_CALLS:
            out.append(Finding(
                path, node.lineno, "R7",
                f"profiler call {name}(...) in {where} — host spans go "
                f"through tracing.span(...), which names them serve.*"))
    return out


def _lint_named_funcs(path: str, names: Set[str],
                      label: str) -> List[Finding]:
    with open(path) as f:
        src = f.read()
    lines = src.splitlines()
    tree = ast.parse(src, path)
    out: List[Finding] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in names:
            out.extend(_check_hot_scope(
                ast.walk(node), path, lines,
                f"{label} {node.name}()"))
    return out


def lint_scheduler(path: str) -> List[Finding]:
    return _lint_named_funcs(path, TICK_FUNCS, "tick-path function")


# engine functions the scheduler tick reaches (speculative _verify_row
# calls eng._pick per rejected position): same packed-mask rules apply
ENGINE_HOT_FUNCS: Set[str] = {"_pick"}


def lint_engine(path: str) -> List[Finding]:
    return _lint_named_funcs(path, ENGINE_HOT_FUNCS,
                             "engine hot function")


def lint_kernel_dispatch(path: str) -> List[Finding]:
    with open(path) as f:
        src = f.read()
    lines = src.splitlines()
    tree = ast.parse(src, path)
    return _check_hot_scope(ast.walk(tree), path, lines,
                            "masked-sample dispatch")


def lint_core_determinism(path: str) -> List[Finding]:
    with open(path) as f:
        src = f.read()
    lines = src.splitlines()
    tree = ast.parse(src, path)
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if _has_pragma(lines, node.lineno):
            continue
        base, name = _call_name(node)
        if (base, name) in CLOCK_BANNED:
            out.append(Finding(
                path, node.lineno, "R3",
                f"wall-clock call {base}.{name}() in core/ — grammar "
                f"state must be reproducible; use time.perf_counter() "
                f"for timing stats only"))
        if base in ("random",) and name in RANDOM_FUNCS:
            out.append(Finding(
                path, node.lineno, "R3",
                f"global-RNG call random.{name}() in core/ — draw from "
                f"an explicitly seeded np.random.Generator passed in by "
                f"the caller"))
        if base in ("np.random", "numpy.random") and name != "default_rng":
            out.append(Finding(
                path, node.lineno, "R3",
                f"global numpy RNG call {base}.{name}() in core/ — "
                f"module-level RNG state makes decode output depend on "
                f"call order; accept a Generator argument instead"))
    return out


def _swallows(handler: ast.ExceptHandler) -> bool:
    """Handler body does nothing but pass / ``...`` (a swallowed
    exception)."""
    for stmt in handler.body:
        if isinstance(stmt, ast.Pass):
            continue
        if isinstance(stmt, ast.Expr) \
                and isinstance(stmt.value, ast.Constant) \
                and stmt.value.value is Ellipsis:
            continue
        return False
    return True


def lint_serving_excepts(path: str) -> List[Finding]:
    with open(path) as f:
        src = f.read()
    lines = src.splitlines()
    tree = ast.parse(src, path)
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if _has_pragma(lines, node.lineno):
            continue
        if node.type is None:
            out.append(Finding(
                path, node.lineno, "R4",
                "bare `except:` in serving/ — catches SystemExit/"
                "KeyboardInterrupt too; catch Exception and map the "
                "failure to an explicit per-request terminal status"))
        elif _swallows(node):
            out.append(Finding(
                path, node.lineno, "R4",
                "swallowed exception (handler body is only pass/...) in "
                "serving/ — a failure here must surface as a request "
                "status (internal_error / rejected), never vanish"))
    return out


def main(argv: List[str]) -> int:
    if argv:
        targets = [os.path.abspath(a) for a in argv]
    else:
        targets = None
    sched = os.path.join(REPO, "src", "repro", "serving", "scheduler.py")
    engine = os.path.join(REPO, "src", "repro", "serving", "engine.py")
    dispatch = os.path.join(REPO, "src", "repro", "kernels",
                            "masked_sample", "ops.py")
    core_dir = os.path.join(REPO, "src", "repro", "core")
    serving_dir = os.path.join(REPO, "src", "repro", "serving")

    findings: List[Finding] = []
    if targets is None or sched in targets:
        findings.extend(lint_scheduler(sched))
    if targets is None or engine in targets:
        findings.extend(lint_engine(engine))
    if targets is None or dispatch in targets:
        findings.extend(lint_kernel_dispatch(dispatch))
    for fn in sorted(os.listdir(core_dir)):
        path = os.path.join(core_dir, fn)
        if fn.endswith(".py") and (targets is None or path in targets):
            findings.extend(lint_core_determinism(path))
    for fn in sorted(os.listdir(serving_dir)):
        path = os.path.join(serving_dir, fn)
        if fn.endswith(".py") and (targets is None or path in targets):
            findings.extend(lint_serving_excepts(path))

    for f in findings:
        print(f)
    if findings:
        print(f"\n{len(findings)} hot-path lint violation(s)",
              file=sys.stderr)
        return 1
    print("hot-path lint: clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))

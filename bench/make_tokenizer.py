#!/usr/bin/env python3
"""Train the benchmark's 16k-token BPE tokenizer once, off the chip.

The corpus is the repository's own ``.py``/``.md`` text (tracked files
outside ``bench/``, in sorted path order) plus 2,000 json strings drawn
by ``GrammarSampler(json, seed=0)``.  ``train_bpe`` learns 16,384 - 256
merges; the tokenizer then has 16,384 byte and merge tokens plus 3
specials.  The merges are written to ``bench/data/tok16k.json``, which
every benchmark run loads and never retrains.

  PYTHONPATH=src python3 bench/make_tokenizer.py   # ~7 minutes on a CPU
"""
import argparse
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

VOCAB = 16384
N_JSON = 2000


def repo_text() -> bytes:
    files = subprocess.run(["git", "ls-files", "*.py", "*.md"], cwd=ROOT,
                           capture_output=True, text=True,
                           check=True).stdout.split()
    files = sorted(f for f in files if not f.startswith("bench/"))
    return b"\n".join((ROOT / f).read_bytes() for f in files)


def main() -> int:
    from repro.core import grammars
    from repro.core.sampling import GrammarSampler
    from repro.tokenizer import train_bpe

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(ROOT / "bench/data/tok16k.json"))
    args = ap.parse_args()
    corpus = repo_text() + b"\n" + GrammarSampler(
        grammars.load("json"), seed=0).corpus(N_JSON)
    t0 = time.perf_counter()
    tok = train_bpe(corpus, vocab_size=VOCAB)
    tok.save(args.out)
    print(f"{len(corpus)} corpus bytes -> V={tok.vocab_size} in "
          f"{time.perf_counter() - t0:.1f} s: {args.out}")
    return 0 if tok.vocab_size == VOCAB + 3 else 1


if __name__ == "__main__":
    sys.exit(main())

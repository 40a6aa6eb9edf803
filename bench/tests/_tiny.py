"""The tiny CPU cell the harness tests drive (not a benchmark cell)."""
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
DATA = os.path.join(BENCH, "tests", "data")


def cell(per_layer=()):
    import run
    with open(os.path.join(DATA, "tiny.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(DATA, "tiny-mix.json")) as f:
        mix = json.load(f)
    with open(os.path.join(DATA, "tiny-check.json")) as f:
        limits = json.load(f)["limits"]
    return run.Cell(name="tiny", chips=1, config=cfg, mix=mix,
                    limits=limits,
                    end_to_end={"setup_s": "s", "output_tok_s": "tokens/s",
                                "itl_p95_ms": "ms", "ttft_p90_ms": "ms",
                                "request_p90_ms": "ms"},
                    per_layer={m: "%" for m in per_layer})

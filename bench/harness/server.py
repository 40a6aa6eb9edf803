"""The system under test, built from a configuration file and a mix.

The configuration's family module (``families/<family>.py``) turns the
file into the program's model.  One ``ServingEngine`` and one
``ContinuousBatchingScheduler`` serve the whole run, warm-up included,
so every program the window drives is compiled before it starts.  Off: ``debug_invariants``, the journal,
fault injection, grammar analysis and device tables.  On: paged KV, the
Pallas kernels, and the prefix cache where the configuration says so.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

import jax

import families
from harness import weights

CONFIG_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def load_config(name: str) -> dict:
    with open(os.path.join(CONFIG_DIR, name + ".json")) as f:
        return json.load(f)


class Server:
    """Engine, scheduler and the seconds each part of set-up took."""

    def __init__(self, cfg: dict, grammars: List[Optional[str]], tok,
                 seed: int, pinned: List[str] = ()):
        from repro.core import grammars as zoo
        from repro.models import build_model
        from repro.serving import ServingEngine

        self.cfg = cfg
        self.setup: Dict[str, float] = {}
        self.model = build_model(families.of(cfg).model_config(cfg))
        t0 = time.perf_counter()
        self.engine = ServingEngine(self.model, None, tok,
                                    max_len=cfg["server"]["max_len"])
        for g in sorted({g for g in grammars if g is not None}):
            self.engine.register_grammar(g, zoo.load(g))
        self.engine.precompute()
        self.setup["trees_s"] = time.perf_counter() - t0
        self.reseed(seed, pinned)

    def reseed(self, seed: int, pinned: List[str] = ()) -> None:
        """Weights drawn from ``seed`` and a fresh scheduler over them:
        an empty pool and prefix cache, the engine's trees kept."""
        from repro.serving.scheduler import ContinuousBatchingScheduler

        srv = self.cfg["server"]
        self.sched = None
        self.engine.params = None
        t0 = time.perf_counter()
        self.engine.params = weights.program_params(self.model, seed)
        jax.block_until_ready(self.engine.params)
        self.setup["weights_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.sched = ContinuousBatchingScheduler(
            self.engine, capacity=srv["slots"], page_size=srv["page_size"],
            n_pages=srv["pool_pages"], prefix_cache=srv["prefix_cache"])
        if srv["prefix_cache"]:
            for p in pinned:
                self.engine.pin_prompt(p)
            self.sched._pin_prompts()
        jax.block_until_ready(self.sched.cache)
        self.setup["pool_s"] = time.perf_counter() - t0

    def busy(self) -> bool:
        s = self.sched
        return bool(s.waiting) or any(x is not None for x in s.slots)

    def drain(self) -> None:
        while self.busy():
            self.sched.step()

    def close(self) -> None:
        """Drop every device buffer the program holds."""
        self.sched = None
        self.engine.params = None

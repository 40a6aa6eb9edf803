"""Constrained serving engine — DOMINO integrated as a first-class feature.

The unit of work is a :class:`~repro.serving.request.Request`:
``prompt + ConstraintSpec + DecodeParams``.  The engine itself owns no
grammar and no decode policy — it owns a **grammar registry**:

    engine = ServingEngine(model, params, tok)
    engine.register_grammar("json", json_grammar)   # one shared TreeCache
    engine.register_grammar("c", c_grammar)         # per grammar
    engine.precompute()                             # warm ALL of them
    r = engine.generate(Request(
        "a config: ",
        ConstraintSpec(grammar="json", mode="domino"),
        DecodeParams(max_tokens=64)))

Each registered grammar gets ONE shared ``TreeCache`` (subterminal trees +
packed-mask memo) reused by every request that references it — sessions
never build trees per request — and ``precompute()`` (paper Algorithm 2)
warms every registered cache off the serving critical path.  A
``ConstraintSpec`` may also carry a ``Grammar`` object directly; it is
auto-registered on first use so repeats still share a cache.

Constraint modes (the rows of the paper's tables), per request:
  unconstrained          plain decoding
  domino                 DOMINO masks, lookahead k (None = ∞, minimally
                         invasive); opportunistic masking optional
  naive                  greedy single-terminal masking (= DOMINO k=0)
  online                 full-vocab online parser checking (llama.cpp/GCD
                         cost profile, identical masks to domino k=∞)
  template               GUIDANCE-style template programs (forced tokens)

Speculation (§3.6) is a per-request ``DecodeParams`` knob: the
grammar-state count model (shared engine-wide, so priors learned by one
request speed up the next) proposes up to ``s`` tokens; ONE decode_step
forward scores [pending || proposals]; the longest verified prefix
commits.  Rollback is a cache-length rewind for full-attention/MLA archs;
ring-buffer (SWA) and recurrent (SSM/hybrid) archs re-feed the accepted
tokens from the pre-speculation cache.

Sampling is per-request: each request draws from its own
``np.random.Generator`` seeded by ``DecodeParams.seed``, so a sampled
request's output never depends on batch composition or admission order.

Back-compat: the legacy surface — ``ServingEngine(model, params, tok,
grammar, EngineConfig(...))`` plus ``generate("prompt")`` — still works
token-for-token for greedy decoding (temperature 0, every existing test
and table row).  The constructor grammar is registered under the name
``"default"`` and the ``EngineConfig`` becomes the engine's
default-``Request`` factory (:meth:`make_request`); a bare string
anywhere a ``Request`` is accepted submits that default request.  One
deliberate semantic change: sampled decoding reseeds per request (it
used to consume a shared engine RNG that advanced across calls), so
repeated identical sampled requests return identical output — pass a
different ``DecodeParams.seed`` per request for best-of-n diversity.

This module keeps the single-request fast path and the template baseline.
Batched serving lives in ``serving/scheduler.py`` (continuous batching
with slot reuse and per-row constraint routing); ``generate_batch``
delegates to it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmask
from repro.core.analysis import (OFF_FRONTIER, AnalysisReport,
                                 DeviceGrammarTable, analyze, enforce)
from repro.core.baselines import TemplateSession
from repro.core.grammar import Grammar
from repro.core.scanner import Scanner
from repro.core.speculation import CountModel, Speculator
from repro.core.trees import TreeCache
from repro.models.model import Model
from repro.serving.request import (ConstraintSpec, DecodeParams, Request,
                                   packed_argmax, select_token)
from repro.serving.session import GenerationResult
from repro.tokenizer import BPETokenizer

DEFAULT_GRAMMAR = "default"


@dataclasses.dataclass
class EngineConfig:
    """Legacy engine-wide configuration.

    Kept as a back-compat shim: it no longer freezes anything into the
    engine — it is split into the engine's default ``ConstraintSpec`` +
    ``DecodeParams`` (see :meth:`constraint_spec` / :meth:`decode_params`)
    and applies only to requests submitted as bare strings.
    """
    mode: str = "domino"              # unconstrained|domino|naive|online|template
    k: Optional[int] = None           # DOMINO lookahead (None = ∞)
    opportunistic: bool = False
    speculative: bool = False
    spec_s: int = 8
    spec_threshold: float = 0.5
    temperature: float = 0.0          # 0 = greedy
    max_tokens: int = 128
    seed: int = 0
    # token healing (§3.5): strip the last `heal` prompt tokens and force
    # the stripped text as a generation prefix (bridge tokens across the
    # prompt boundary become available)
    heal: int = 0

    def constraint_spec(self, grammar_ref) -> ConstraintSpec:
        return ConstraintSpec(grammar=grammar_ref, mode=self.mode,
                              k=self.k, opportunistic=self.opportunistic,
                              heal=self.heal)

    def decode_params(self) -> DecodeParams:
        return DecodeParams(temperature=self.temperature,
                            max_tokens=self.max_tokens, seed=self.seed,
                            speculative=self.speculative,
                            spec_s=self.spec_s,
                            spec_threshold=self.spec_threshold)


@dataclasses.dataclass
class _RowPolicy:
    """Selection policy for the single-request path (the scheduler passes
    the Session itself, which exposes the same fields)."""
    temperature: float
    opportunistic: bool
    decode: DecodeParams
    _rng: Optional[np.random.Generator] = None

    @property
    def rng(self) -> np.random.Generator:
        if self._rng is None:
            self._rng = self.decode.make_rng()
        return self._rng


class DeviceTableSet:
    """All certified grammars' :class:`DeviceGrammarTable`\\ s merged into
    ONE device-resident pair of arrays so the scheduler's fused loop can
    gather any row's mask with a single index, whatever grammar the row
    decodes under:

      ``mask_dev``  — ``(total_states, ceil(V/32))`` uint32 packed masks
      ``trans_dev`` — ``(total_states, V)`` int32 token→next-state table

    Per-grammar state ids are offset into the concatenated range
    (``offsets[name]``); ``OFF_FRONTIER`` (negative) edges stay negative
    after offsetting, so a state id ``< 0`` ALWAYS means "host path".
    Host mirrors (``mask_host`` / ``trans_host``) serve the scheduler's
    per-token bookkeeping (transition lookups, opportunistic bit tests)
    without device readbacks.  Built once by
    :meth:`ServingEngine.build_device_tables`; immutable afterwards."""

    def __init__(self, tables: Dict[str, DeviceGrammarTable]):
        names = sorted(tables)
        self.tables = {n: tables[n] for n in names}
        self.offsets: Dict[str, int] = {}
        masks, trans = [], []
        off = 0
        for n in names:
            t = tables[n]
            self.offsets[n] = off
            masks.append(t.mask_table)
            tr = t.trans.astype(np.int32, copy=True)
            tr[tr >= 0] += off          # remap edges into the concat range
            trans.append(tr)
            off += t.n_states
        self.n_states = off
        self.mask_host = np.concatenate(masks, axis=0)
        self.trans_host = np.concatenate(trans, axis=0)
        self.mask_dev = jnp.asarray(self.mask_host)
        self.trans_dev = jnp.asarray(self.trans_host)

    @property
    def n_bytes(self) -> int:
        return int(self.mask_host.nbytes + self.trans_host.nbytes)

    def sid_for(self, name: str, checker) -> int:
        """Global state id for ``checker``'s current state under grammar
        ``name``, or ``OFF_FRONTIER`` when the grammar has no table or
        the state is outside the certified frontier."""
        off = self.offsets.get(name)
        if off is None:
            return OFF_FRONTIER
        sid = self.tables[name].sid_for(checker)
        return sid + off if sid >= 0 else OFF_FRONTIER


class ServingEngine:
    def __init__(self, model: Model, params, tok: BPETokenizer,
                 grammar: Optional[Grammar] = None,
                 cfg: Optional[EngineConfig] = None,
                 tree_cache: Optional[TreeCache] = None,
                 count_model: Optional[CountModel] = None,
                 max_len: int = 1024,
                 analysis_policy: str = "off",
                 max_adhoc_grammars: int = 32,
                 device_tables: bool = False):
        self.model = model
        self.params = params
        self.tok = tok
        self.grammar = grammar
        self.cfg = cfg or EngineConfig()
        self.max_len = max_len
        # registration-time static analysis (repro.core.analysis):
        #   off    — skip entirely (default; analysis costs ~seconds per
        #            grammar, opt in for serving deployments)
        #   warn   — run, report problems as a RuntimeWarning, register
        #   strict — run, refuse to register a grammar with any problem
        #            (raises AnalysisError BEFORE the registry commits)
        self.analysis_policy = analysis_policy
        self.analysis_reports: Dict[str, AnalysisReport] = {}
        # device-resident decode tables (ISSUE 8): when enabled,
        # precompute() uploads each CLEANLY-certified grammar's packed
        # mask + transition tables so the scheduler's fused loop can run
        # N tokens per host sync.  Grammars whose certificate is dirty
        # (non-finite closure, merge conflicts, truncations, traps) are
        # silently left on the host path — correctness never depends on
        # certification, only the sync cadence does.
        self.enable_device_tables = device_tables
        self.device_tables: Dict[str, DeviceGrammarTable] = {}
        self._device_table_set: Optional[DeviceTableSet] = None
        # refcounts + ad-hoc bookkeeping so rotating per-request Grammar
        # objects does not leak (TreeCache, mask memo) pairs forever
        self._grammar_refs: Dict[str, int] = {}
        self._adhoc_order: List[str] = []
        self.max_adhoc_grammars = max_adhoc_grammars
        # grammar registry: name -> (Grammar, shared TreeCache).  The
        # cache slot may be None: the legacy constructor registers its
        # grammar lazily when the default mode never consults trees, so
        # an unconstrained/template engine does no tree work (old
        # behavior) while per-request specs can still name "default"
        self.registry: Dict[str, Tuple[Grammar, Optional[TreeCache]]] = {}
        if grammar is not None:
            if (cfg or EngineConfig()).mode in ("domino", "naive",
                                                "online"):
                self.register_grammar(DEFAULT_GRAMMAR, grammar,
                                      tree_cache=tree_cache)
            else:
                self.registry[DEFAULT_GRAMMAR] = (grammar, None)
        # engine defaults: what a bare-string submission decodes with
        self.default_constraint = self.cfg.constraint_spec(
            DEFAULT_GRAMMAR if grammar is not None else None)
        self.default_decode = self.cfg.decode_params()
        # back-compat attribute: the default grammar's shared cache (only
        # when the default mode actually consumes trees, as before)
        if grammar is not None and self.cfg.mode in ("domino", "naive",
                                                     "online"):
            self.tree_cache = self.registry[DEFAULT_GRAMMAR][1]
        else:
            self.tree_cache = None
        # speculation: ONE count model engine-wide (priors transfer across
        # requests); Speculator instances are pooled per (s, threshold) so
        # identical knobs share the proposal-chain memo
        self.count_model = count_model or CountModel()
        self._speculators: Dict[Tuple[int, float], Speculator] = {}
        self.speculator = self._speculator_for(self.default_decode)
        # engine-default prompts (system preambles / few-shot headers)
        # registered via pin_prompt(): a prefix-cache-enabled scheduler
        # prefills and PINS their full KV pages at warm() time, so the
        # very first live request sharing the preamble already hits
        self.pinned_prompts: List[str] = []
        # counters of the last generate_batch call's scheduler
        self.last_batch_stats: Optional[Dict[str, Any]] = None
        # engine-level rng: used only by the template baseline (which has
        # no Request); request sampling is per-session
        self.rng = np.random.default_rng(self.cfg.seed)
        self._v = tok.vocab_size   # model logits may be vocab-padded
        # jit'd steps (compiled once per (batch, s) shape)
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode_step)
        # rollback safety (DESIGN.md §Arch-applicability)
        blocks = self._all_block_kinds()
        self._needs_refeed = any(
            b in ("mamba1", "mamba2", "swa") for b in blocks)

    def _all_block_kinds(self) -> List[str]:
        head, reps, group, tail = self.model.cfg.layer_program
        return list(head) + list(group) + list(tail)

    # -- grammar registry --------------------------------------------------------

    def register_grammar(self, name: str, grammar: Grammar,
                         tree_cache: Optional[TreeCache] = None,
                         policy: Optional[str] = None) -> TreeCache:
        """Register ``grammar`` under ``name`` with ONE shared TreeCache
        (subterminal trees + packed-mask memo).  Every request whose
        ``ConstraintSpec.grammar == name`` builds its checker against
        this cache — no per-request tree construction.

        Under ``analysis_policy`` (or the per-call ``policy`` override)
        ``warn``/``strict`` the grammar is statically analyzed against
        the engine's vocabulary FIRST — a strict failure raises
        :class:`~repro.core.analysis.AnalysisError` and registers
        nothing.  The report lands in ``self.analysis_reports[name]``.

        Re-registering a name with the SAME grammar object is a no-op
        that bumps its refcount (see :meth:`unregister_grammar`); a
        different grammar replaces the entry.  Returns the cache."""
        prev = self.registry.get(name)
        if prev is not None and prev[0] is grammar and prev[1] is not None:
            self._grammar_refs[name] = self._grammar_refs.get(name, 0) + 1
            return prev[1]
        tc = tree_cache if tree_cache is not None else TreeCache(
            Scanner(grammar), list(self.tok.vocab))
        pol = policy if policy is not None else self.analysis_policy
        if pol != "off":
            report = analyze(grammar, list(self.tok.vocab),
                             self.tok.eos_id, name=name, tree_cache=tc)
            enforce(report, pol)       # strict: raises before committing
            self.analysis_reports[name] = report
        self.registry[name] = (grammar, tc)
        self._grammar_refs[name] = self._grammar_refs.get(name, 0) + 1
        return tc

    def unregister_grammar(self, name: str) -> bool:
        """Drop one reference to ``name``; when the count reaches zero the
        registry entry — (Grammar, TreeCache) pair, mask memo and
        analysis report — is released.  Engines that rotate through
        ad-hoc grammars must pair each ``register_grammar`` with one
        ``unregister_grammar`` or rely on the ad-hoc LRU cap.  Returns
        True when the entry was fully removed."""
        if name not in self.registry:
            raise KeyError(f"grammar {name!r} is not registered")
        n = self._grammar_refs.get(name, 1) - 1
        if n > 0:
            self._grammar_refs[name] = n
            return False
        self.registry.pop(name, None)
        self._grammar_refs.pop(name, None)
        self.analysis_reports.pop(name, None)
        if name in self._adhoc_order:
            self._adhoc_order.remove(name)
        if self.tree_cache is not None and name == DEFAULT_GRAMMAR:
            self.tree_cache = None
        return True

    def resolve_grammar(self, ref) -> Tuple[Optional[Grammar],
                                            Optional[TreeCache]]:
        """Resolve a ConstraintSpec grammar reference to (grammar,
        shared TreeCache).  Accepts a registered name, a Grammar object
        (auto-registered keyed by identity so repeats share the cache),
        or None.  Auto-registered ad-hoc grammars live in a bounded LRU
        (``max_adhoc_grammars``): once it is full the oldest entry whose
        refcount is 1 (i.e. held only by the auto-registration itself)
        is evicted, so per-request throwaway grammars cannot leak
        (TreeCache, memo) pairs without bound."""
        if ref is None:
            return None, None
        if isinstance(ref, str):
            entry = self.registry.get(ref)
            if entry is None:
                raise KeyError(
                    f"grammar {ref!r} is not registered (have: "
                    f"{sorted(self.registry)}); call "
                    f"engine.register_grammar({ref!r}, grammar) first")
            if entry[1] is None:       # lazily-registered: build now
                self._grammar_refs.pop(ref, None)  # re-count the rebuild
                return entry[0], self.register_grammar(ref, entry[0])
            return entry
        # Grammar object: reuse an existing registration, else auto-add
        for name, (g, tc) in self.registry.items():
            if g is ref:
                if tc is None:
                    self._grammar_refs.pop(name, None)
                    return g, self.register_grammar(name, g)
                if name in self._adhoc_order:      # LRU touch
                    self._adhoc_order.remove(name)
                    self._adhoc_order.append(name)
                return g, tc
        name = f"grammar@{id(ref):x}"
        self.register_grammar(name, ref)
        self._adhoc_order.append(name)
        while len(self._adhoc_order) > self.max_adhoc_grammars:
            victim = next((n for n in self._adhoc_order
                           if self._grammar_refs.get(n, 1) <= 1), None)
            if victim is None:         # every ad-hoc entry is pinned
                break
            self._adhoc_order.remove(victim)
            self.registry.pop(victim, None)
            self._grammar_refs.pop(victim, None)
            self.analysis_reports.pop(victim, None)
        return self.registry[name]

    def analyze_grammar(self, name: str, policy: Optional[str] = None,
                        **kwargs) -> AnalysisReport:
        """(Re-)run static analysis for a registered grammar against the
        engine's vocabulary, on the registry's SHARED TreeCache (so the
        trees it builds are the trees serving will use).  ``kwargs`` pass
        through to :func:`repro.core.analysis.analyze` (clamp,
        max_states, ...).  The report is stored and policy-enforced."""
        grammar, tc = self.resolve_grammar(name)
        report = analyze(grammar, list(self.tok.vocab), self.tok.eos_id,
                         name=name, tree_cache=tc, **kwargs)
        self.analysis_reports[name] = report
        enforce(report, policy if policy is not None
                else self.analysis_policy)
        return report

    def precompute(self) -> Dict[str, float]:
        """Offline warm path: build every reachable subterminal tree for
        EVERY registered grammar now (paper Algorithm 2) so serving never
        constructs trees on the critical path.  Each per-grammar
        TreeCache is shared across all of that grammar's sessions.

        Under ``analysis_policy != "off"`` this is also the analysis
        sweep: any registered grammar without a stored report is analyzed
        (and the policy enforced) here — reports in
        ``self.analysis_reports``, aggregate cost in the returned
        ``analysis_seconds``."""
        out = {"positions": 0.0, "seconds": 0.0, "analysis_seconds": 0.0}
        for name, (grammar, tc) in list(self.registry.items()):
            if tc is None:             # lazily registered, never resolved
                continue
            if self.analysis_policy != "off" \
                    and name not in self.analysis_reports:
                report = analyze(grammar, list(self.tok.vocab),
                                 self.tok.eos_id, name=name,
                                 tree_cache=tc)
                self.analysis_reports[name] = report
                out["analysis_seconds"] += report.analysis_time_s
                enforce(report, self.analysis_policy)
            stats = tc.precompute()
            out["positions"] += stats["positions"]
            out["seconds"] += stats["seconds"]
        if self.enable_device_tables:
            out["device_table_seconds"] = self.build_device_tables()
        return out

    def pin_prompt(self, prompt: str) -> None:
        """Register an engine-default prompt (shared system preamble /
        few-shot header) for prefix pinning: a prefix-cache-enabled
        scheduler's ``warm()`` prefills its whole-page prefix once and
        pins the pages against eviction.  A no-op for schedulers without
        ``prefix_cache=True``."""
        if prompt not in self.pinned_prompts:
            self.pinned_prompts.append(prompt)

    def build_device_tables(self) -> float:
        """Build + upload a :class:`DeviceGrammarTable` for every
        registered grammar whose closure certificate is CLEAN (finite,
        zero merge conflicts, zero hypothesis truncations, zero traps,
        and an overall-``ok()`` report); dirty grammars stay host-only.

        A grammar with a STORED report is judged by that report — never
        re-analyzed behind its back — so a certificate that was
        downgraded (e.g. by a stricter re-analysis, or a test doctoring
        conflicts in) durably excludes the grammar from the device path.
        Grammars without a stored report are analyzed here with
        ``emit_device_table=True`` on their shared TreeCache.  Returns
        the seconds spent analyzing."""
        spent = 0.0
        for name, (grammar, tc) in list(self.registry.items()):
            if tc is None or name in self.device_tables:
                continue
            report = self.analysis_reports.get(name)
            if report is None:
                report = analyze(grammar, list(self.tok.vocab),
                                 self.tok.eos_id, name=name,
                                 tree_cache=tc, emit_device_table=True)
                self.analysis_reports[name] = report
                spent += report.analysis_time_s
            elif report.device_table is None:
                # stored report: trust its certificate.  Dirty -> skip
                # WITHOUT re-analysis (the downgrade stands); clean but
                # table-less (analyzed without emit) -> re-run with emit.
                if (not report.closure.finite or report.n_mask_conflicts
                        or report.n_hyp_truncations or not report.ok()):
                    continue
                report = analyze(grammar, list(self.tok.vocab),
                                 self.tok.eos_id, name=name,
                                 tree_cache=tc, emit_device_table=True)
                self.analysis_reports[name] = report
                spent += report.analysis_time_s
            if report.device_table is not None and report.ok():
                self.device_tables[name] = report.device_table
                tc.device_table = report.device_table
                self._device_table_set = None      # rebuild lazily
        return spent

    @property
    def device_table_set(self) -> Optional[DeviceTableSet]:
        """The merged device upload over every certified grammar (None
        until :meth:`build_device_tables` certifies at least one)."""
        if self._device_table_set is None and self.device_tables:
            self._device_table_set = DeviceTableSet(self.device_tables)
        return self._device_table_set

    # -- request / checker factory -----------------------------------------------

    def make_request(self, prompt: str,
                     constraint: Optional[ConstraintSpec] = None,
                     decode: Optional[DecodeParams] = None,
                     extra_inputs: Optional[Dict[str, Any]] = None
                     ) -> Request:
        """Default-``Request`` factory: unspecified parts come from the
        legacy engine-level ``EngineConfig`` / constructor grammar, which
        is how bare-string submissions keep their exact old behavior."""
        return Request(prompt=prompt,
                       constraint=constraint or self.default_constraint,
                       decode=decode or self.default_decode,
                       extra_inputs=extra_inputs)

    def _coerce(self, request: Union[str, Request]) -> Request:
        return (self.make_request(request) if isinstance(request, str)
                else request)

    def _eos_for(self, spec: ConstraintSpec) -> int:
        return spec.eos_id if spec.eos_id is not None else self.tok.eos_id

    def _checker_from_spec(self, spec: ConstraintSpec,
                           heal_prefix: str = ""):
        grammar = tc = None
        if spec.grammar is not None and spec.mode != "unconstrained":
            grammar, tc = self.resolve_grammar(spec.grammar)
        return spec.make_checker(grammar, list(self.tok.vocab),
                                 self._eos_for(spec), tree_cache=tc,
                                 heal_prefix=heal_prefix)

    def _make_checker(self, heal_prefix: str = ""):
        """Checker factory for the engine-DEFAULT constraint (kept as a
        seam: tests monkeypatch it to inject checker stubs into both the
        single-request and the scheduler path)."""
        return self._checker_from_spec(self.default_constraint,
                                       heal_prefix)

    def _prep(self, req: Request):
        """Shared request preamble: encode, apply token healing (§3.5),
        build the checker from the grammar registry.  ``generate`` and
        the scheduler's ``submit`` both go through here so their outputs
        stay token-for-token identical."""
        spec = req.constraint
        prompt_ids = self.tok.encode(req.prompt) or [self.tok.bos_id]
        prompt_ids, heal_prefix = spec.prep_prompt(prompt_ids,
                                                   self.tok.vocab)
        if spec is self.default_constraint:
            checker = self._make_checker(heal_prefix)
        else:
            checker = self._checker_from_spec(spec, heal_prefix)
        return prompt_ids, checker

    def _prep_request(self, prompt: str):
        """Back-compat alias: prep the engine-default request."""
        return self._prep(self.make_request(prompt))

    def make_session(self, rid: int, request: Union[str, Request],
                     extra_inputs=None):
        """Create a scheduler :class:`~repro.serving.session.Session`
        carrying the request's full per-row decode policy (used by
        ``ContinuousBatchingScheduler.submit``)."""
        from repro.serving.session import Session
        req = self._coerce(request)
        prompt_ids, checker = self._prep(req)
        dp = req.decode
        # request-level side inputs first, call-level overrides on top
        merged = dict(req.extra_inputs or {})
        merged.update(extra_inputs or {})
        return Session(rid=rid, prompt=req.prompt, prompt_ids=prompt_ids,
                       checker=checker, budget=dp.max_tokens,
                       eos_id=self._eos_for(req.constraint), decode=dp,
                       opportunistic=req.constraint.opportunistic,
                       speculator=self._speculator_for(dp), request=req,
                       extra_inputs=merged or None)

    def _speculator_for(self, dp: DecodeParams) -> Optional[Speculator]:
        # speculation is greedy-verified: at temperature>0 proposals
        # almost never match the sampled pick (no forward savings), and
        # every mismatched verify position would burn a per-request RNG
        # draw whose count depends on the SHARED count model's state —
        # breaking the guarantee that a sampled request's output is
        # independent of batch composition.  Sampled rows decode plain.
        if not dp.speculative or dp.temperature > 0.0:
            return None
        key = (dp.spec_s, dp.spec_threshold)
        sp = self._speculators.get(key)
        if sp is None:
            sp = Speculator(self.count_model, s=dp.spec_s,
                            threshold=dp.spec_threshold)
            self._speculators[key] = sp
        return sp

    # -- sampling -----------------------------------------------------------------

    def _default_policy(self) -> _RowPolicy:
        pol = _RowPolicy(temperature=self.cfg.temperature,
                         opportunistic=self.cfg.opportunistic,
                         decode=self.default_decode)
        pol._rng = self.rng            # template/legacy path: engine rng
        return pol

    def _select(self, logits: np.ndarray, mask: Optional[np.ndarray],
                policy=None) -> int:
        pol = policy or self._default_policy()
        return select_token(logits, mask, pol.temperature,
                            pol.rng if pol.temperature > 0.0 else None)

    def _pick(self, logits: np.ndarray, checker, premask=None,
              policy=None) -> Tuple[Optional[int], int, float]:
        """Select the next token under the row's constraint + decode
        policy (``policy``: a Session or _RowPolicy; None = engine
        defaults).

        Returns (token, intervened?, mask_seconds).  ``token`` is None
        when the checker reached a dead end (no legal token, EOS
        included) — callers surface this as ``GenerationResult.dead_end``
        instead of silently emitting grammar-violating output.
        ``premask`` is a mask the caller already built from the checker's
        current state (e.g. the scheduler's host/device-overlapped
        prebuild); its build time was accounted at build site, so it does
        not count here.  Packed uint32 masks (the pipeline's native row
        format) stay packed on the greedy branch — bit test on the
        candidate + legal-id argmax — and are unpacked to bool only for
        temperature>0 sampling.
        """
        pol = policy or self._default_policy()
        if checker is None:
            return self._select(logits, None, pol), 0, 0.0
        mask_t = 0.0
        greedy = pol.temperature <= 0.0
        if pol.opportunistic and greedy:
            cand = int(logits.argmax())
            t0 = time.perf_counter()
            ok = checker.check_token(cand)
            mask_t += time.perf_counter() - t0
            if ok:
                return cand, 0, mask_t
        bits = mask = None
        if premask is not None:
            if premask.dtype == np.uint32:
                bits = premask
            else:
                mask = premask                 # bool premask (stub checkers)
        elif greedy and hasattr(checker, "mask_bits"):
            t0 = time.perf_counter()
            bits = checker.mask_bits()
            mask_t += time.perf_counter() - t0
        else:
            t0 = time.perf_counter()
            mask = checker.mask()
            mask_t += time.perf_counter() - t0
        if bits is not None:
            if greedy:
                raw = int(logits.argmax())
                if bitmask.get_bit(bits, raw):
                    return raw, 0, mask_t      # legal argmax: no unpack
                tok = packed_argmax(logits, bits, self._v)
                if tok is None:
                    # the checker invariant makes this unreachable for
                    # sound grammars; report it rather than force EOS
                    return None, 0, mask_t
                return tok, 1, mask_t          # raw argmax was illegal
            # temperature>0 host sampling is the one place bits may
            # widen; greedy/verify paths above stay packed
            mask = bitmask.unpack(bits, self._v)  # hotpath-lint: allow
        if not mask.any():
            return None, 0, mask_t
        tok = self._select(logits, mask, pol)
        intervened = int(tok != int(logits.argmax()))
        return tok, intervened, mask_t

    # -- generation -----------------------------------------------------------------

    def generate(self, request: Union[str, Request],
                 extra_inputs: Optional[Dict[str, Any]] = None
                 ) -> GenerationResult:
        """Serve one request on the single-request fast path.  ``request``
        is a :class:`Request` or a bare prompt string (= the engine's
        default request)."""
        t_start = time.perf_counter()
        req = self._coerce(request)
        dp = req.decode
        eos_id = self._eos_for(req.constraint)
        policy = _RowPolicy(temperature=dp.temperature,
                            opportunistic=req.constraint.opportunistic,
                            decode=dp)
        speculator = self._speculator_for(dp)
        prompt_ids, checker = self._prep(req)
        cache = self.model.init_cache(1, self.max_len)
        inputs = {"tokens": jnp.asarray([prompt_ids], jnp.int32)}
        # request-level side inputs first, call-level overrides on top
        inputs.update(req.extra_inputs or {})
        inputs.update(extra_inputs or {})

        model_t = 0.0
        mask_t = 0.0
        n_fwd = 0
        n_int = 0
        n_prop = 0
        n_acc = 0
        out_ids: List[int] = []

        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, inputs, cache)
        logits = np.asarray(logits)[0, -1][:self._v]
        model_t += time.perf_counter() - t0
        n_fwd += 1

        finished = False
        dead_end = False
        status: Optional[str] = None       # non-ok terminal override
        error: Optional[str] = None
        budget = dp.max_tokens
        while budget > 0 and not finished and not dead_end \
                and status is None:
            # fault edges shared with the scheduler path: a request-level
            # deadline bounds wall time, and non-finite logits terminate
            # with an explicit status instead of committing garbage
            if dp.deadline_s is not None \
                    and time.perf_counter() - t_start > dp.deadline_s:
                status = "deadline_exceeded"
                error = f"deadline {dp.deadline_s:g}s exceeded"
                break
            if not np.all(np.isfinite(logits)):
                status = "internal_error"
                error = "non-finite logits from device step"
                break
            # ---- try speculative fast path -------------------------------------
            if (speculator is not None and checker is not None
                    and hasattr(checker, "clone")):
                tok0, intervened, dt = self._pick(logits, checker,
                                                  policy=policy)
                mask_t += dt
                if tok0 is None:
                    dead_end = True
                    break
                n_int += intervened
                if tok0 == eos_id:
                    finished = True
                    checker.advance(tok0)
                    break
                speculator.observe(checker.state_key(), tok0)
                checker.advance(tok0)
                out_ids.append(tok0)
                budget -= 1
                proposals = speculator.propose(checker)
                n_prop += len(proposals)
                feed = [tok0] + proposals
                # static verify width (spec_s + 1): TPU-friendly single
                # compiled program; pad positions are rolled back below
                n_pad = (1 + dp.spec_s) - len(feed)
                feed_p = feed + [self.tok.pad_id] * n_pad
                cache_before = cache
                t0 = time.perf_counter()
                lg_multi, cache = self._decode(
                    self.params, cache, jnp.asarray([feed_p], jnp.int32))
                lg_multi = np.asarray(lg_multi)[0][:, :self._v]
                model_t += time.perf_counter() - t0
                n_fwd += 1
                # verify proposals against (masked) argmax at each position
                accepted = 0
                ch = checker
                for i, prop in enumerate(proposals):
                    if budget <= 0:
                        break
                    if not np.all(np.isfinite(lg_multi[i])):
                        status = "internal_error"
                        error = ("non-finite logits in speculative "
                                 "verify window")
                        break
                    # fast verification: if the raw argmax equals the
                    # proposal, an O(token) opportunistic legality check
                    # replaces the full tree-walk mask
                    tok_i = None
                    if dp.temperature <= 0.0 \
                            and int(lg_multi[i].argmax()) == prop:
                        t0 = time.perf_counter()
                        ok = ch.check_token(prop)
                        mask_t += time.perf_counter() - t0
                        if ok:
                            tok_i = prop
                    if tok_i is None:
                        tok_i, intervened, dt = self._pick(lg_multi[i], ch,
                                                           policy=policy)
                        mask_t += dt
                        if tok_i is None:
                            dead_end = True
                            break
                        n_int += intervened
                    if tok_i != prop:
                        break
                    speculator.observe(ch.state_key(), tok_i)
                    ch.advance(tok_i)
                    accepted += 1
                    if tok_i == eos_id:
                        finished = True
                        break
                    out_ids.append(tok_i)
                    budget -= 1
                n_acc += accepted
                rejected = len(proposals) - accepted
                if rejected > 0 or n_pad > 0:
                    if self._needs_refeed:
                        # recompute from the pre-speculation cache (exact
                        # length: recurrent/ring state cannot host pads)
                        t0 = time.perf_counter()
                        lg_re, cache = self._decode(
                            self.params, cache_before,
                            jnp.asarray([feed[:1 + accepted]], jnp.int32))
                        logits = np.asarray(lg_re)[0, -1][:self._v]
                        model_t += time.perf_counter() - t0
                        n_fwd += 1
                    else:
                        cache = self.model.rollback(cache,
                                                    rejected + n_pad)
                        logits = lg_multi[accepted]
                else:
                    logits = lg_multi[len(proposals)]
                continue

            # ---- plain path ------------------------------------------------------
            tok, intervened, dt = self._pick(logits, checker, policy=policy)
            mask_t += dt
            if tok is None:
                dead_end = True
                break
            n_int += intervened
            if checker is not None:
                checker.advance(tok)
            if tok == eos_id:
                finished = True
                break
            out_ids.append(tok)
            budget -= 1
            t0 = time.perf_counter()
            lg, cache = self._decode(self.params, cache,
                                     jnp.asarray([[tok]], jnp.int32))
            logits = np.asarray(lg)[0, -1][:self._v]
            model_t += time.perf_counter() - t0
            n_fwd += 1

        return GenerationResult(
            status=status or ("dead_end" if dead_end else "ok"),
            error=error,
            text=self.tok.decode(out_ids),
            token_ids=out_ids,
            n_forward_passes=n_fwd,
            n_tokens=len(out_ids),
            n_interventions=n_int,
            n_spec_proposed=n_prop,
            n_spec_accepted=n_acc,
            mask_time_s=mask_t,
            model_time_s=model_t,
            wall_time_s=time.perf_counter() - t_start,
            finished=finished,
            dead_end=dead_end,
            mask_cache_hits=getattr(checker, "n_mask_memo_hits", 0),
            n_hyp_truncations=getattr(checker, "n_hyp_truncations", 0),
            max_hyp_fanout=getattr(checker, "max_hyp_fanout", 1),
        )

    # -- batched serving -------------------------------------------------------------

    def generate_batch(self, requests: List[Union[str, Request]],
                       max_batch: Optional[int] = None,
                       paged: Optional[bool] = None,
                       page_size: Optional[int] = None,
                       n_pages: Optional[int] = None,
                       queue_limit: Optional[int] = None,
                       queue_timeout_s: Optional[float] = None,
                       default_deadline_s: Optional[float] = None,
                       fault_injector=None,
                       debug_invariants: bool = False,
                       device_loop: bool = False,
                       sync_n: int = 8,
                       journal=None,
                       supervisor=None,
                       prefix_cache: bool = False
                       ) -> List[GenerationResult]:
        """Serve ``requests`` (Requests or bare prompt strings) through
        the continuous-batching scheduler.  Rows may mix grammars,
        constraint modes, EOS ids, budgets and sampling policies freely —
        each row decodes under its own ``ConstraintSpec``/``DecodeParams``.

        ``max_batch`` caps the decode batch (slots); extra requests wait
        in the admission queue and reuse slots as earlier requests
        finish.  All architectures are supported: recurrent/ring rows are
        admitted by exact-length prefill and speculation uses per-row
        refeed.  On pure full-attention/MLA stacks the KV cache is paged
        by default (``paged``/``page_size``/``n_pages`` size the pool; an
        undersized pool exerts admission backpressure instead of OOM).
        Call :meth:`precompute` first to keep tree construction off the
        serving critical path.

        Fault-tolerance knobs pass straight through to the scheduler:
        ``queue_limit`` / ``queue_timeout_s`` bound the waiting queue,
        ``default_deadline_s`` bounds wall time for requests that carry
        no ``DecodeParams.deadline_s``, ``fault_injector`` wires a
        :class:`~repro.serving.faults.FaultInjector`, and
        ``debug_invariants`` audits every tick boundary.  Every request
        gets a result regardless — non-ok outcomes carry an explicit
        ``status`` / ``error``.

        ``device_loop=True`` enables the device-resident fused decode
        loop for rows whose grammar carries a clean device table (build
        them first: ``ServingEngine(..., device_tables=True)`` +
        :meth:`precompute`); ``sync_n`` is the number of decode steps
        fused per host sync.  Rows without a certified table decode on
        the host path, token-for-token identical to ``device_loop=False``.

        ``journal`` wires a
        :class:`~repro.serving.journal.TokenJournal` (crash-consistent
        WAL — see :meth:`restore`); ``supervisor`` a
        :class:`~repro.serving.supervisor.DegradationSupervisor`
        (watchdogs + the fused->host->dense degradation ladder).

        ``prefix_cache=True`` (paged only) shares whole KV pages across
        requests with identical token prefixes through a radix tree with
        copy-on-write refcounting — admissions skip prefill for the
        cached prefix and re-prefill only the tail (observationally
        pure: outputs are bitwise-identical to a cold cache).

        The scheduler's operational counters (``stats()``: degrades,
        retries, watchdog trips, engine resets, prefix hits) are kept in
        ``self.last_batch_stats``.
        """
        from repro.serving.scheduler import ContinuousBatchingScheduler
        cap = min(len(requests), max_batch) if max_batch else len(requests)
        kwargs = {}
        if paged is not None:
            kwargs["paged"] = paged
        if page_size is not None:
            kwargs["page_size"] = page_size
        if n_pages is not None:
            kwargs["n_pages"] = n_pages
        sched = ContinuousBatchingScheduler(
            self, capacity=cap, queue_limit=queue_limit,
            queue_timeout_s=queue_timeout_s,
            default_deadline_s=default_deadline_s,
            fault_injector=fault_injector,
            debug_invariants=debug_invariants,
            device_loop=device_loop, sync_n=sync_n,
            journal=journal, supervisor=supervisor,
            prefix_cache=prefix_cache, **kwargs)
        if prefix_cache:
            # install engine-default pinned prompts before admission
            # (precompute() is the caller's job and may already be done)
            sched._pin_prompts()
        sessions = [sched.submit(r) for r in requests]
        sched.run()
        self.last_batch_stats = sched.stats()
        return [s.result for s in sessions]

    def restore(self, journal_path: str, max_batch: Optional[int] = None,
                journal=None, **scheduler_kwargs):
        """Cold-restart recovery: replay the crash journal at
        ``journal_path`` and return a scheduler pre-loaded with every
        journaled request — terminal requests carry their journaled
        result, live requests are reconstructed (prompt + validated
        committed prefix replayed through a fresh concrete checker, RNG
        stream restored) and queued for re-prefill through the
        recompute-preemption machinery.  Call ``run()`` (or ``step()``)
        on the returned scheduler to finish them; greedy rows complete
        bitwise-identical to an uninterrupted run.

        Pass ``journal`` (typically ``TokenJournal(journal_path)``, which
        truncates any torn tail) to keep the resumed run durable in the
        SAME file — replayed state is journaled idempotently, so repeated
        crash/restore cycles converge instead of compounding."""
        from repro.serving.journal import replay_journal
        from repro.serving.scheduler import ContinuousBatchingScheduler
        entries = replay_journal(journal_path)
        cap = scheduler_kwargs.pop("capacity", None)
        if cap is None:
            live = sum(1 for e in entries.values()
                       if e.terminal is None and e.recoverable)
            cap = max(1, min(live, max_batch) if max_batch else live)
        sched = ContinuousBatchingScheduler(
            self, capacity=cap, journal=journal, **scheduler_kwargs)
        for entry in entries.values():
            sched.adopt(entry)
        return sched

    # -- template mode ------------------------------------------------------------

    def generate_template(self, prompt: str, parts) -> GenerationResult:
        """GUIDANCE-style template execution (baseline for Fig. 2/Table 2)."""
        t_start = time.perf_counter()
        session = TemplateSession(parts, list(self.tok.vocab),
                                  self.tok.eos_id, self.tok.encode_greedy)
        prompt_ids = self.tok.encode(prompt) or [self.tok.bos_id]
        cache = self.model.init_cache(1, self.max_len)
        t0 = time.perf_counter()
        logits, cache = self._prefill(
            self.params, {"tokens": jnp.asarray([prompt_ids], jnp.int32)},
            cache)
        logits = np.asarray(logits)[0, -1][:self._v]
        model_t = time.perf_counter() - t0
        n_fwd = 1
        out_ids: List[int] = []
        budget = self.cfg.max_tokens
        while budget > 0:
            action, payload = session.next_action()
            if action == "done":
                break
            if action == "force":
                if not payload:
                    continue
                out_ids.extend(payload)
                budget -= len(payload)
                t0 = time.perf_counter()
                lg, cache = self._decode(
                    self.params, cache, jnp.asarray([payload], jnp.int32))
                logits = np.asarray(lg)[0, -1][:self._v]
                model_t += time.perf_counter() - t0
                n_fwd += 1
                continue
            # gen under slot mask
            tok = self._select(logits, payload)
            session.feed(tok)
            if tok == self.tok.eos_id:
                continue  # slot ended; do not emit eos into output
            out_ids.append(tok)
            budget -= 1
            t0 = time.perf_counter()
            lg, cache = self._decode(self.params, cache,
                                     jnp.asarray([[tok]], jnp.int32))
            logits = np.asarray(lg)[0, -1][:self._v]
            model_t += time.perf_counter() - t0
            n_fwd += 1
        return GenerationResult(
            text=self.tok.decode(out_ids), token_ids=out_ids,
            n_forward_passes=n_fwd, n_tokens=len(out_ids),
            n_interventions=session.forced_tokens,
            n_spec_proposed=0, n_spec_accepted=0,
            mask_time_s=0.0, model_time_s=model_t,
            wall_time_s=time.perf_counter() - t_start, finished=True)

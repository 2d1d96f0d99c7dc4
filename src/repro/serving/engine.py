"""Workflow serving engine on real JAX devices.

The benchmark substrate (repro.core.executor) evaluates scheduling
policies on proxy costs — the paper's own methodology.  This engine is
the production path: FATE's placements drive actual model execution on
virtual devices, each bound to a chip and holding resident model params
and per-group recurrent/KV prefix state.  Model residency switches place
real param trees on the chip; prefix reuse is tracked per device; stage
execution runs real prefill + decode steps.  Wall times, read once the
generated tokens are ready, feed back into the execution state, so the
scheduler sees measured (not proxy) τ.
"""
from __future__ import annotations

import dataclasses
import time
from collections import Counter
from functools import partial
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.calibration import CalibrationProfile, StageObservation
from repro.core.faults import FaultInjector, TransientStageFailure
from repro.core.planner import Placement
from repro.core.state import ExecutionState
from repro.core.workflow import DEFAULT_PROFILES, Stage, Workflow
from repro.models.families import build_model
from repro.spans import span


def jit_steps(model) -> tuple[Callable, Callable]:
    """The engine's jitted ``(prefill, decode_step)`` for ``model``.

    Both run on the device their committed arguments live on, so one
    pair serves every chip a bundle is placed on. Prefill consumes the
    cache it is given (donated, its rows written in place): rebind the
    one it returns. Decode consumes nothing. The cache arrays its step
    returns unchanged (all but the rows it writes, where the model keeps
    those apart) come back as the very arrays given, not as copies.
    ``decode.lower`` lowers the jitted step, for ahead-of-time compiles.
    """
    @partial(jax.jit, donate_argnums=(2,))
    def prefill_fn(params, tokens, cache):
        return model.prefill(params, tokens, cache)

    @jax.jit
    def decode_fn(params, token, cache, pos):
        logits, new = model.decode_step(params, token, cache, pos)
        # an array returned as it was given leaves the program as None
        return logits, jax.tree.map(lambda n, c: None if n is c else n,
                                    new, cache)

    def decode(params, token, cache, pos):
        logits, new = decode_fn(params, token, cache, pos)
        return logits, jax.tree.map(lambda n, c: c if n is None else n,
                                    new, cache, is_leaf=lambda n: n is None)

    decode.lower = decode_fn.lower
    return prefill_fn, decode


@dataclasses.dataclass
class ModelBundle:
    """A servable model: config + weights + step functions."""
    name: str
    cfg: Any
    params: Any
    prefill: Callable
    decode: Callable
    model: Any = None
    init: Optional[Callable] = None     # jitted ``model.init``

    @classmethod
    def create(cls, name: str, cfg, seed: int = 0) -> "ModelBundle":
        """Build and initialize the model (params on JAX's default
        device), jit its prefill/decode step functions, and return the
        servable bundle.

        The init is one jitted program: run op by op, a full-width
        model compiles each op per leaf shape and keeps every float32
        intermediate on the device."""
        model = build_model(cfg)
        init = jax.jit(model.init)
        params = init(jax.random.PRNGKey(seed))
        return cls(name, cfg, params, *jit_steps(model), model=model,
                   init=init)

    def replica(self, name: str, seed: int) -> "ModelBundle":
        """Another bundle of this config with weights from ``seed``,
        sharing this bundle's jitted init and steps (one compile serves
        both)."""
        return dataclasses.replace(
            self, name=name, params=self.init(jax.random.PRNGKey(seed)))


class ChipWeights:
    """Per-chip copies of model params, shared by the virtual devices
    bound to each chip.

    A copy is made when a virtual device on that chip first makes the
    model resident, and dropped when no virtual device on the chip
    still has it resident.  A copy on the chip that already holds
    ``bundle.params`` shares their buffers.

    ``bytes_copied_to`` counts, per ``(model, chip id)``, the bytes of
    every copy made onto a chip that did not hold the model, and
    ``bytes_copied`` their total; a copy that shares the home buffers,
    or that another virtual device on the chip already made, adds
    nothing.  A ``fate.weights.copy`` span (``model``, ``chip``,
    ``bytes``) marks each such copy's dispatch: ``jax.device_put`` is
    asynchronous, so the transfer's time is that of the copy on the
    device trace, not the span's length.
    """

    def __init__(self):
        self._copies: dict[tuple[str, jax.Device], Any] = {}
        self._holders: dict[tuple[str, jax.Device], set[int]] = {}
        self.bytes_copied_to: Counter[tuple[str, int]] = Counter()

    @property
    def bytes_copied(self) -> int:
        """Bytes of every copy made onto a chip that did not hold the
        model, over all models and chips."""
        return sum(self.bytes_copied_to.values())

    def acquire(self, did: int, bundle: ModelBundle,
                device: jax.Device) -> Any:
        """Params of ``bundle`` committed to ``device``, held for
        virtual device ``did``."""
        key = (bundle.name, device)
        if key not in self._copies:
            leaves = jax.tree.leaves(bundle.params)
            if all(x.devices() == {device} for x in leaves):
                self._copies[key] = jax.device_put(bundle.params, device)
            else:
                nbytes = sum(x.nbytes for x in leaves)
                with span("fate.weights.copy", model=bundle.name,
                          chip=device.id, bytes=nbytes):
                    self._copies[key] = jax.device_put(bundle.params,
                                                       device)
                self.bytes_copied_to[(bundle.name, device.id)] += nbytes
        self._holders.setdefault(key, set()).add(did)
        return self._copies[key]

    def release(self, did: int, model: str, device: jax.Device) -> None:
        """Virtual device ``did`` no longer holds ``model``; drop the
        chip's copy if it was the last holder."""
        key = (model, device)
        holders = self._holders[key]
        holders.discard(did)
        if not holders:
            del self._holders[key]
            del self._copies[key]

    def placed(self) -> set[tuple[str, jax.Device]]:
        """``(model, device)`` pairs that currently have a copy."""
        return set(self._copies)


@dataclasses.dataclass
class VirtualDevice:
    """One scheduling unit bound to a chip (``device``): holds at most
    one resident model's params, committed to that chip, plus a marker
    for each prefix it has kept, keyed by (group, model, queries)."""
    did: int
    device: jax.Device
    weights: ChipWeights
    resident: Optional[str] = None
    params: Any = None
    prefix_caches: dict = dataclasses.field(default_factory=dict)

    def ensure_resident(self, bundle: ModelBundle) -> bool:
        """Make ``bundle`` this device's resident model; returns True
        if a switch happened.

        A switch drops the prefix markers of other models, releases the
        previous model's chip copy and places ``bundle``'s params on
        this device's chip (see :class:`ChipWeights`); what a switch
        costs is that copy, if one is made.
        """
        if self.resident == bundle.name:
            return False
        self.prefix_caches = {k: v for k, v in self.prefix_caches.items()
                              if k[1] == bundle.name}
        if self.resident is not None:
            self.weights.release(self.did, self.resident, self.device)
        self.params = self.weights.acquire(self.did, bundle, self.device)
        self.resident = bundle.name
        return True


@dataclasses.dataclass
class StageResult:
    """One executed stage: outputs, wall time, and the calibration
    features the cost-model fitter consumes (tokens in/out, residency
    switches, warm-prefix coverage — see
    :meth:`ServingEngine.observations`)."""
    sid: str
    device_ids: tuple[int, ...]
    tokens_out: jax.Array           # [num_queries, gen_len]
    wall_s: float                   # dispatch to tokens ready
    switched: bool
    prefix_hit: bool
    # calibration features (measure -> fit -> profile loop)
    model: str = ""
    queries: int = 0
    prompt_tokens: int = 0          # per query
    output_tokens: int = 0          # per query
    switches: int = 0               # residency switches across shards
    switch_bytes: int = 0           # weight bytes the switches copied
    cache_bytes: int = 0            # fresh KV cache made, over shards
    prefix_fraction: float = 0.0    # fraction of queries with warm hit
    # per-shard tokens in placement order, each on its device's chip
    shards: tuple[jax.Array, ...] = ()


class ServingEngine:
    """Executes one workflow's stages per a policy's placements.

    ``calibration`` loads a
    :class:`~repro.core.calibration.CalibrationProfile`: its model
    profiles label the logged observations by family, and
    :meth:`run_workflow` asserts that the execution state the policy
    plans against carries the same constants, so engine and planner
    cannot diverge silently.  Without one the hand-set
    ``DEFAULT_PROFILES`` label the observations.

    Every executed stage is appended to ``log`` with its calibration
    features; :meth:`observations` converts the log into the
    :func:`repro.core.calibration.fit_profile` input format, closing
    the measure → fit → profile loop.

    ``faults`` optionally arms a deterministic
    :class:`~repro.core.faults.FaultInjector`: stage executions the
    injector targets raise
    :class:`~repro.core.faults.TransientStageFailure`, and
    :meth:`run_workflow` retries them (same placement, fresh attempt
    counter) up to the plan's ``max_retries`` — the real-execution
    mirror of the scheduler's simulated retry path.

    Virtual device ``i`` is bound to ``chips[i % len(chips)]``
    (default ``jax.devices()``): on one chip every virtual device
    shares it, on several the placements spread over them.  A shard
    runs on its virtual device's chip, with its prompts, cache and
    the resident params committed there. Every array a shard reads is
    made on its chip or sent from the host: the fresh cache is filled
    on the shard's chip, never on another and copied over, and the
    decode positions are host scalars.
    """

    def __init__(self, models: dict[str, ModelBundle], n_devices: int,
                 *, gen_len: int = 8, prompt_len: int = 32,
                 calibration: Optional[CalibrationProfile] = None,
                 faults: Optional[FaultInjector] = None,
                 chips: Optional[Sequence[jax.Device]] = None):
        self.models = models
        chips = list(chips) if chips is not None else jax.devices()
        self.weights = ChipWeights()
        self.devices = [VirtualDevice(i, chips[i % len(chips)],
                                      self.weights)
                        for i in range(n_devices)]
        self.gen_len = gen_len
        self.prompt_len = prompt_len
        self.calibration = calibration
        self.faults = faults
        self.n_fault_retries = 0
        # per-model profiles the observations are labelled from: the
        # loaded calibration's fit, or the hand-set defaults
        self._profiles = (calibration.model_profiles()
                          if calibration is not None
                          else dict(DEFAULT_PROFILES))
        self.log: list[StageResult] = []

    def observations(self) -> list[StageObservation]:
        """Calibration observations for every logged stage execution.

        The engine runs each shard on its own virtual device without
        cross-device tensor movement, so ``transfer_ktokens`` is zero —
        the fitter marks the transfer coefficient as defaulted rather
        than fitting it from a feature that never varies.
        """
        out: list[StageObservation] = []
        for r in self.log:
            prof = self._profiles.get(r.model)
            out.append(StageObservation(
                model=r.model,
                family=prof.family if prof is not None else "generic",
                queries=r.queries,
                prompt_tokens=float(r.prompt_tokens),
                output_tokens=float(r.output_tokens),
                switches=r.switches,
                prefix_fraction=r.prefix_fraction,
                transfer_ktokens=0.0,
                wall_s=r.wall_s))
        return out

    def run_stage(self, wf: Workflow, stage: Stage,
                  placement: Placement,
                  prompts: jax.Array, attempt: int = 0) -> StageResult:
        """prompts: [num_queries, prompt_len] int32 token ids.

        ``attempt`` is the retry ordinal the fault injector keys on
        (only attempt 0 is failure-eligible, so retries always
        converge); an injected fault raises
        :class:`~repro.core.faults.TransientStageFailure` before any
        device state is touched.
        """
        if self.faults is not None:
            frac = self.faults.failure_fraction(
                wf.wid, stage.sid, placement.devices, attempt)
            if frac is not None:
                raise TransientStageFailure(
                    f"injected fault: stage {wf.wid}/{stage.sid} on "
                    f"devices {placement.devices} failed at "
                    f"{frac:.0%} of its run (attempt {attempt})")
        with span("fate.stage", wid=wf.wid, sid=stage.sid):
            return self._run_stage(stage, placement, prompts)

    def _run_stage(self, stage: Stage, placement: Placement,
                   prompts: jax.Array) -> StageResult:
        bundle = self.models[stage.model]
        t0 = time.perf_counter()
        copied_before = self.weights.bytes_copied
        n_switches = 0
        hit_queries = 0
        cache_bytes = 0
        outs = []
        q0 = 0
        max_len = self.prompt_len + self.gen_len
        for did, nq in zip(placement.devices, placement.shard_sizes):
            if nq == 0:
                continue
            dev = self.devices[did]
            with span("fate.stage.switch", model=stage.model, did=did,
                      chip=dev.device.id):
                if dev.ensure_resident(bundle):
                    n_switches += 1
            nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
                bundle.model.init_cache(nq, max_len, abstract=True)))
            cache_bytes += nbytes
            with span("fate.stage.put", chip=dev.device.id,
                      cache_bytes=nbytes):
                shard = jax.device_put(prompts[q0: q0 + nq], dev.device)
                q0 += nq
                cache_key = (stage.prefix_group, stage.model, nq)
                # prefix reuse is emulated at the bookkeeping level: a
                # saved marker records the hit (κ state the scheduler
                # scored for), but prefill below always starts fresh —
                # replaying a saved KV would need per-query prefix
                # alignment the tiny-model substrate doesn't model.
                if (stage.cache_reuse and stage.prefix_group is not None
                        and cache_key in dev.prefix_caches):
                    hit_queries += nq
                # filled op by op on the shard's chip; the put commits
                # it there and copies nothing
                with jax.default_device(dev.device):
                    fresh = jax.device_put(
                        bundle.model.init_cache(nq, max_len), dev.device)
            with span("fate.stage.prefill"):
                logits, kv = bundle.prefill(dev.params, shard, fresh)
                if stage.keep_cache and stage.prefix_group is not None:
                    dev.prefix_caches[cache_key] = True
                tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
            gen = [tok]
            pos = shard.shape[1]
            # a model with no stacked KV cache (RWKV, the hybrids)
            # attends in no fused pass
            attn = getattr(bundle.model, "decode_pass", None)
            with span("fate.stage.decode",
                      attn=attn(kv, dev.device.platform) if attn
                      else "parts"):
                for step in range(self.gen_len - 1):
                    logits, kv = bundle.decode(dev.params, tok, kv,
                                               np.int32(pos + step))
                    tok = jnp.argmax(logits, -1).astype(jnp.int32)
                    gen.append(tok)
            outs.append(jnp.concatenate(gen, axis=1))
        # shards on other chips are gathered to the first shard's chip;
        # the clock stops once every generated token is ready
        with span("fate.stage.gather"):
            tokens = (jnp.concatenate([jax.device_put(o, outs[0].device)
                                       for o in outs], axis=0)
                      if outs else jnp.zeros((0, self.gen_len), jnp.int32))
        with span("fate.stage.ready"):
            tokens.block_until_ready()
        wall_s = time.perf_counter() - t0
        n_q = int(tokens.shape[0])
        res = StageResult(
            stage.sid, placement.devices, tokens,
            wall_s, n_switches > 0, hit_queries > 0,
            model=stage.model, queries=n_q,
            prompt_tokens=self.prompt_len, output_tokens=self.gen_len,
            switches=n_switches,
            switch_bytes=self.weights.bytes_copied - copied_before,
            cache_bytes=cache_bytes,
            prefix_fraction=hit_queries / n_q if n_q else 0.0,
            shards=tuple(outs))
        self.log.append(res)
        return res

    def run_workflow(self, wf: Workflow, policy, state: ExecutionState,
                     prompts: jax.Array) -> dict[str, StageResult]:
        """Execute the full DAG: plan with the policy, run stages on
        real devices in dependency order, update real execution state.

        The state's clock is the wall time since this call began: once
        a stage's tokens are ready, its devices' free times,
        residencies and warm prefixes are written at that time.  With a
        loaded calibration profile the state's model profiles must
        carry the profile's constants; this is asserted before the
        first plan.
        """
        with span("fate.workflow", wid=wf.wid):
            return self._run_workflow(wf, policy, state, prompts)

    def _run_workflow(self, wf: Workflow, policy, state: ExecutionState,
                      prompts: jax.Array) -> dict[str, StageResult]:
        if self.calibration is not None:
            self.calibration.assert_consistent(state.profiles)
        results: dict[str, StageResult] = {}
        completed: set[str] = set()
        t_start = time.perf_counter()
        while len(completed) < len(wf.stages):
            ready = [sid for sid in wf.topo_order
                     if sid not in completed
                     and all(p in completed for p in wf.stages[sid].parents)]
            with span("fate.plan"):
                placements = policy.plan(wf, state, ready)
            if not placements:
                raise RuntimeError(
                    f"policy {type(policy).__name__} returned no "
                    f"placement for workflow {wf.wid!r} with ready "
                    f"stages {ready}")
            for p in placements:
                if p.sid in completed:
                    continue
                stage = wf.stages[p.sid]
                max_retries = (self.faults.plan.max_retries
                               if self.faults is not None else 0)
                for attempt in range(max_retries + 1):
                    try:
                        res = self.run_stage(wf, stage, p, prompts,
                                             attempt=attempt)
                        break
                    except TransientStageFailure:
                        if attempt >= max_retries:
                            raise
                        self.n_fault_retries += 1
                with span("fate.state"):
                    results[p.sid] = res
                    completed.add(p.sid)
                    now = time.perf_counter() - t_start
                    state.now = now
                    for d in p.devices:
                        state.set_free_at(d, now)
                        state.set_resident(d, stage.model)
                        if stage.keep_cache:
                            state.warm_prefix(d, stage.prefix_group,
                                              stage.model, wf.num_queries,
                                              now)
                    state.output_loc[(wf.wid, p.sid)] = p.devices
                    state.completed.add((wf.wid, p.sid))
        return results

"""Jitted engine slot step — the device-resident half of the fused path.

``EngineStep`` is a jax pytree view of ``ClusterState``'s dynamic columns
(state codes, warming clocks, queues, utilization, idle counters, MRU
model cache) plus the static hardware facts the step math needs.  Three
jitted kernels cover the interpreted engine surface:

* :func:`warm_step` — warming progression (``Engine._progress_warming``);
* :func:`apply_single` — the grouped decision apply for servers that
  receive exactly ONE task this slot: switch cost + energy, MRU update,
  queue push and the wait/work decomposition, all inside one dispatch;
* :func:`close_step` — queue drain, utilization/idle bookkeeping and the
  per-server power draw of ``Engine._finish_slot``.

Each jits a named function, so its XLA module reads
``jit_engine_warm_step``, ``jit_engine_apply_single`` or
``jit_engine_close_step`` in a profile.  ``JaxStepper`` counts the arrays
each dispatch moves and the bytes it uploads
(``device.transfers{dir=...,layer=engine}``,
``device.h2d_bytes{layer=engine}``).

Every op mirrors the numpy engine's float64 expression order bitwise
(elementwise IEEE ops only — reductions such as the per-region power sum
and the metrics totals stay on the host over the returned arrays, so the
accumulation order is literally the numpy engine's).  Same-server
conflicts and slots whose targeted server went inactive keep falling back
to the numpy path exactly as ``Engine._apply_decision`` does; the numpy
engine remains the golden-parity oracle (``Engine(step_backend="jax")``
selects this module, ``tests/test_fused_step.py`` pins exact-metric
trajectory parity).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitize
from repro.obs import runtime as obs_rt
from repro.sim.cluster import SWITCH_POWER_FRAC
from repro.sim.state import (ACTIVE, NO_MODEL, WARM_SLOTS, WARMING,
                             ClusterState, _WARM_HIT_S)


def _model_switch_s() -> float:
    from repro.sim.cluster import MODEL_SWITCH_S
    return MODEL_SWITCH_S


# the columns ``EngineStep.from_state`` uploads on every dispatch
DYNAMIC_FIELDS = ("state", "warm_remaining_s", "queue_s", "util",
                  "idle_slots", "current_model", "warm_models")


def static_arrays(st: ClusterState):
    """The step's static hardware triple as device arrays.  ``speed`` is
    precomputed with host numpy: XLA rewrites division by the literal
    112.0 into a multiply-by-reciprocal, a last-ulp divergence from the
    numpy engine's true division."""
    return (jnp.asarray(np.maximum(st.tflops / 112.0, 0.1)),
            jnp.asarray(st.power_w), jnp.asarray(st.switch_scale))


@partial(jax.tree_util.register_dataclass,
         data_fields=["state", "warm_remaining_s", "queue_s", "util",
                      "idle_slots", "current_model", "warm_models",
                      "speed", "power_w", "switch_scale"],
         meta_fields=[])
@dataclasses.dataclass
class EngineStep:
    """Pytree view of ``ClusterState`` for the jitted slot step."""

    # dynamic columns (written back after each jitted call)
    state: jax.Array             # (S,) int8
    warm_remaining_s: jax.Array  # (S,) float64
    queue_s: jax.Array           # (S,) float64
    util: jax.Array              # (S,) float64
    idle_slots: jax.Array        # (S,) int64
    current_model: jax.Array     # (S,) int16
    warm_models: jax.Array       # (S, W) int16
    # static hardware facts (read-only).  ``speed`` is precomputed on the
    # host: XLA rewrites division by the literal 112.0 into a
    # multiply-by-reciprocal, which is a last-ulp divergence from the
    # numpy engine's true division — host numpy keeps parity bitwise.
    speed: jax.Array             # (S,) float64 max(tflops/112, 0.1)
    power_w: jax.Array           # (S,) float64
    switch_scale: jax.Array      # (S,) float64

    @classmethod
    def from_state(cls, st: ClusterState,
                   statics=None) -> "EngineStep":
        """Build the view from a numpy ``ClusterState``.  ``statics`` is
        an optional cached ``(speed, power_w, switch_scale)`` device
        triple (``JaxStepper`` uploads it once per run)."""
        if statics is None:
            statics = static_arrays(st)
        speed, power_w, switch_scale = statics
        return cls(
            **{name: jnp.asarray(getattr(st, name))
               for name in DYNAMIC_FIELDS},
            speed=speed, power_w=power_w, switch_scale=switch_scale)

    def write_back(self, st: ClusterState,
                   fields=DYNAMIC_FIELDS) -> None:
        """Sync dynamic columns into the numpy ``ClusterState`` (the host
        mirror the schedulers/oracle fallback read); callers narrow
        ``fields`` to the columns their kernel actually wrote."""
        for name in fields:
            getattr(st, name)[...] = np.asarray(getattr(self, name))


def warm_step_impl(step: EngineStep, slot_s, *,
                   checks: bool = False) -> EngineStep:
    """Warming servers progress toward ACTIVE (whole-array, exact
    ``Engine._progress_warming`` semantics)."""
    if checks:
        from jax.experimental import checkify
        checkify.check(jnp.all(step.warm_remaining_s >= 0.0),
                       "sanitize: negative warming clock entering "
                       "warm_step")
    warming = step.state == WARMING
    rem = jnp.where(warming, step.warm_remaining_s - slot_s,
                    step.warm_remaining_s)
    done = warming & (rem <= 0)
    return dataclasses.replace(
        step,
        state=jnp.where(done, jnp.int8(ACTIVE), step.state),
        warm_remaining_s=jnp.where(done, 0.0, rem))


def apply_single_impl(step: EngineStep, gs, mids, work_raw, valid, *,
                      checks: bool = False):
    """Grouped apply for servers receiving exactly one task: returns the
    updated step plus the per-row (switch s, energy J, wait s, work s)
    channels.  Rows are padded to a shape bucket; padded rows carry
    ``gs == n_servers`` and scatter with ``mode="drop"`` — which is why
    the sanitized variant runs user+float checks but NOT index_checks
    (the padding is deliberately out of bounds)."""
    if checks:
        from jax.experimental import checkify
        n_servers = step.speed.shape[0]
        checkify.check(jnp.all(gs >= 0),
                       "sanitize: negative server id in grouped apply")
        checkify.check(jnp.all(~valid | (gs < n_servers)),
                       "sanitize: valid row targets an out-of-range "
                       "server id in grouped apply")
        checkify.check(jnp.all(step.queue_s >= 0.0),
                       "sanitize: negative queue depth entering grouped "
                       "apply")
        checkify.check(jnp.all(~valid | (work_raw >= 0.0)),
                       "sanitize: negative work seconds on a valid row")
    speed = step.speed[gs]
    rows = step.warm_models[gs]                       # (K, W) int16
    warm_hit = (rows == mids[:, None]).any(axis=1)
    cost = jnp.where(warm_hit, step.switch_scale[gs] * _WARM_HIT_S,
                     step.switch_scale[gs] * _model_switch_s())
    sw = jnp.where(step.current_model[gs] == mids, 0.0, cost)
    sw = jnp.where(valid, sw, 0.0)
    energy = jnp.where(sw > 0,
                       sw * step.power_w[gs] * SWITCH_POWER_FRAC, 0.0)
    wk = jnp.where(valid, work_raw / speed, 0.0)
    wait = jnp.where(valid, step.queue_s[gs] + sw, 0.0)

    # MRU model-cache update (``ClusterState.note_model_rows``)
    mids16 = mids.astype(step.current_model.dtype)
    keep = (rows != mids16[:, None]) & (rows != NO_MODEL)
    order = jnp.argsort(~keep, axis=1, stable=True)
    kept = jnp.take_along_axis(rows, order, axis=1)
    n_keep = keep.sum(axis=1)
    cols = [mids16]
    for k in range(WARM_SLOTS - 1):
        cols.append(jnp.where(n_keep > k, kept[:, k],
                              jnp.int16(NO_MODEL)).astype(rows.dtype))
    new_warm = jnp.stack(cols, axis=1)

    step = dataclasses.replace(
        step,
        queue_s=step.queue_s.at[gs].add(sw + wk, mode="drop"),
        current_model=step.current_model.at[gs].set(mids16, mode="drop"),
        warm_models=step.warm_models.at[gs].set(new_warm, mode="drop"))
    return step, sw, energy, wait, wk


def close_step_impl(step: EngineStep, slot_s, *, checks: bool = False):
    """Queue drain + utilization/idle bookkeeping + per-server power
    draw (``Engine._finish_slot``'s whole-array block).  The per-region
    power reduction stays on the host (``ClusterState._segsum``'s
    sequential-within-segment order is the parity contract)."""
    if checks:
        from jax.experimental import checkify
        checkify.check(slot_s > 0.0,
                       "sanitize: non-positive slot length in close_step")
        checkify.check(jnp.all(step.queue_s >= 0.0),
                       "sanitize: negative queue depth entering "
                       "close_step")
    act = step.state == ACTIVE
    busy = jnp.minimum(step.queue_s, slot_s)
    util = jnp.where(act, busy / slot_s, step.util)
    idle = jnp.where(act, jnp.where(util > 0.05, 0, step.idle_slots + 1),
                     step.idle_slots)
    queue = jnp.where(act, jnp.maximum(0.0, step.queue_s - slot_s),
                      step.queue_s)
    power_j = jnp.where(act, (0.1 + 0.9 * util) * step.power_w * slot_s,
                        0.0)
    return dataclasses.replace(step, queue_s=queue, util=util,
                               idle_slots=idle), power_j, act


# Production entries: checks=False compiles to the historical jaxprs.
# Named functions, not partials, so the XLA modules carry stable names.
def engine_warm_step(step: EngineStep, slot_s) -> EngineStep:
    return warm_step_impl(step, slot_s, checks=False)


def engine_apply_single(step: EngineStep, gs, mids, work_raw, valid):
    return apply_single_impl(step, gs, mids, work_raw, valid, checks=False)


def engine_close_step(step: EngineStep, slot_s):
    return close_step_impl(step, slot_s, checks=False)


warm_step = jax.jit(engine_warm_step)
apply_single = jax.jit(engine_apply_single)
close_step = jax.jit(engine_close_step)
# Sanitized variants: module-level partials give sanitize.checkified a
# stable identity to cache the checkify compile under.  user+float only:
# apply_single's padded rows are deliberately out of range for the
# mode="drop" scatters, so index_checks would false-positive by design.
_warm_step_checked = partial(warm_step_impl, checks=True)
_apply_single_checked = partial(apply_single_impl, checks=True)
_close_step_checked = partial(close_step_impl, checks=True)
_ENGINE_ERRORS = "float|user"


def row_bucket(n: int) -> int:
    """Pad size for per-slot row channels (single-task servers): powers
    of two — a handful of compiled shapes per run."""
    return 1 << max(int(n - 1).bit_length(), 4)


class JaxStepper:
    """Host-side driver for the jitted step: owns the ``EngineStep``
    view, pads/buckets the per-slot row channels and writes results back
    into the numpy ``ClusterState`` mirror after each dispatch.  The
    static hardware arrays are uploaded once and reused across every
    dispatch of the run; only the dynamic columns each kernel touches
    round-trip."""

    def __init__(self, state: ClusterState):
        self.state = state
        self._static = None

    @staticmethod
    def _kernels():
        """The (warm, apply, close) triple for the current sanitize
        mode, resolved per dispatch so ``REPRO_SANITIZE`` /
        ``sanitize.force`` flips take effect mid-process."""
        if sanitize.enabled():
            obs_rt.count("engine.sanitize.dispatch")
            return (sanitize.checkified(_warm_step_checked,
                                        errors=_ENGINE_ERRORS),
                    sanitize.checkified(_apply_single_checked,
                                        errors=_ENGINE_ERRORS),
                    sanitize.checkified(_close_step_checked,
                                        errors=_ENGINE_ERRORS))
        return warm_step, apply_single, close_step

    def _make_step(self, *operands) -> EngineStep:
        """The step view for one dispatch; counts its upload: the dynamic
        columns plus the call's own ``operands`` (and the static triple
        on the run's first dispatch)."""
        st = self.state
        if self._static is None:
            with jax.enable_x64(True):
                self._static = static_arrays(st)
            obs_rt.count_transfer("h2d", "engine", lambda: self._static)
        obs_rt.count_transfer(
            "h2d", "engine",
            lambda: [getattr(st, name) for name in DYNAMIC_FIELDS]
            + list(operands))
        return EngineStep.from_state(st, self._static)

    def _write_back(self, step: EngineStep, fields, *outputs) -> None:
        """Write ``fields`` back into the host mirror; counts the
        download of those columns and of the returned ``outputs``."""
        step.write_back(self.state, fields=fields)
        obs_rt.count_transfer(
            "d2h", "engine",
            lambda: [getattr(self.state, name) for name in fields]
            + list(outputs))

    def progress_warming(self, slot_s: float) -> None:
        st = self.state
        if not (st.state == WARMING).any():
            return
        obs_rt.count_new_shape("engine.retrace.warm_step",
                               str(st.n_servers))
        obs_rt.count("engine.host_sync.warm_step")
        warm_fn, _, _ = self._kernels()
        slot = np.float64(slot_s)
        with jax.enable_x64(True):
            step = warm_fn(self._make_step(slot), jnp.asarray(slot))
            self._write_back(step, ("state", "warm_remaining_s"))

    def apply_single_rows(self, gs: np.ndarray, mids: np.ndarray,
                          work_raw: np.ndarray):
        """Apply one task to each (distinct) server ``gs[k]``; returns
        (switch s, energy J, wait s, work s) per row, bitwise equal to
        the numpy grouped apply."""
        st = self.state
        k = gs.size
        bucket = row_bucket(k)
        obs_rt.count_new_shape("engine.retrace.apply_single",
                               f"{bucket}x{st.n_servers}")
        obs_rt.count("engine.host_sync.apply_single")
        pad = bucket - k
        s_total = st.n_servers
        rows = (np.pad(gs.astype(np.int64), (0, pad),
                       constant_values=s_total),    # OOB -> dropped
                np.pad(mids.astype(np.int32), (0, pad)),
                np.pad(work_raw.astype(np.float64), (0, pad)),
                np.pad(np.ones(k, bool), (0, pad)))
        _, apply_fn, _ = self._kernels()
        with jax.enable_x64(True):
            step, sw, energy, wait, wk = apply_fn(
                self._make_step(*rows), *[jnp.asarray(a) for a in rows])
            out = (np.asarray(sw), np.asarray(energy), np.asarray(wait),
                   np.asarray(wk))
            self._write_back(step, ("queue_s", "current_model",
                                    "warm_models"), *out)
            return tuple(a[:k] for a in out)

    def close_slot(self, slot_s: float):
        """Drain/bill the slot; returns the per-server power draw (J)
        and active mask for the host-side regional reduction."""
        st = self.state
        obs_rt.count_new_shape("engine.retrace.close_step",
                               str(st.n_servers))
        obs_rt.count("engine.host_sync.close_step")
        _, _, close_fn = self._kernels()
        slot = np.float64(slot_s)
        with jax.enable_x64(True):
            step, power_j, act = close_fn(self._make_step(slot),
                                          jnp.asarray(slot))
            power_j, act = np.asarray(power_j), np.asarray(act)
            self._write_back(step, ("queue_s", "util", "idle_slots"),
                             power_j, act)
            return power_j, act

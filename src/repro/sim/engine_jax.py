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
``jit_engine_close_step`` in a profile.  Operands and results cross the
host-device link packed per dtype: a dispatch uploads one float64 and one
int32 buffer (the dynamic columns its kernel reads, then the call's own
operands), the entry unpacks them into an ``EngineStep`` inside the jit,
and packs what the kernel wrote into one float64 and one int32 output,
read back together.  A transfer costs about the same whatever its size
at fleet widths, so two buffers each way replace one array per column.
The static hardware triple is uploaded once per run.  ``JaxStepper``
counts the buffers each dispatch moves and the bytes it uploads
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
import math
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


# the dynamic columns and their dtypes in the host mirror (``ClusterState``)
DYNAMIC_FIELDS = {"state": jnp.int8, "warm_remaining_s": jnp.float64,
                  "queue_s": jnp.float64, "util": jnp.float64,
                  "idle_slots": jnp.int64, "current_model": jnp.int16,
                  "warm_models": jnp.int16}

# The dynamic columns each kernel reads from and writes back to the host
# mirror, as (float64 buffer, int32 buffer) of its packed operands/results.
WARM_IO = (("warm_remaining_s",), ("state",))
APPLY_IO = (("queue_s",), ("current_model", "warm_models"))
CLOSE_READS = (("queue_s", "util"), ("state", "idle_slots"))
CLOSE_WRITES = (("queue_s", "util"), ("idle_slots",))


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
    """Pytree view of ``ClusterState`` for the jitted slot step, built
    inside each jitted entry from its packed operands; a dynamic column
    the entry's kernel does not read is ``None``."""

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


# Packed entries: each takes the static triple and one float64 and one
# int32 buffer that hold, in the order of its ``*_IO`` table, the columns
# its kernel reads and then the call's own operands.  It unpacks them,
# runs the unchanged ``*_impl`` body and packs what the kernel wrote: the
# table's written columns, then the per-row or per-server outputs.
def _unpack(statics, reads, floats, ints):
    """The step over the ``reads`` columns at the front of the two
    buffers, cast back to the mirror's dtypes (the columns not read stay
    ``None``), and the rest of each buffer."""
    speed, power_w, switch_scale = statics
    step = dict.fromkeys(DYNAMIC_FIELDS)
    rest = []
    for names, buf in zip(reads, (floats, ints)):
        at = 0
        for name in names:
            shape = speed.shape + ((WARM_SLOTS,) if name == "warm_models"
                                   else ())
            size = math.prod(shape)
            step[name] = buf[at:at + size].reshape(shape).astype(
                DYNAMIC_FIELDS[name])
            at += size
        rest.append(buf[at:])
    return EngineStep(**step, speed=speed, power_w=power_w,
                      switch_scale=switch_scale), rest


def _pack(step, writes, *outputs):
    """The ``writes`` columns of ``step`` and the float64 ``outputs`` as
    one float64 and one int32 result buffer."""
    floats, ints = writes
    return (jnp.concatenate([getattr(step, name).ravel() for name in floats]
                            + list(outputs)),
            jnp.concatenate([getattr(step, name).ravel().astype(jnp.int32)
                             for name in ints]))


def _warm_packed(statics, floats, ints, *, checks):
    step, (slot_s, _) = _unpack(statics, WARM_IO, floats, ints)
    return _pack(warm_step_impl(step, slot_s[0], checks=checks), WARM_IO)


def _apply_packed(statics, floats, ints, *, checks):
    step, (work_raw, rows) = _unpack(statics, APPLY_IO, floats, ints)
    gs, mids, valid = rows.reshape(3, -1)
    step, *channels = apply_single_impl(
        step, gs.astype(jnp.int64), mids, work_raw, valid.astype(bool),
        checks=checks)
    return _pack(step, APPLY_IO, *channels)


def _close_packed(statics, floats, ints, *, checks):
    step, (slot_s, _) = _unpack(statics, CLOSE_READS, floats, ints)
    step, power_j, _ = close_step_impl(step, slot_s[0], checks=checks)
    return _pack(step, CLOSE_WRITES, power_j)


# Production entries: checks=False.  Named functions, not partials, so
# the XLA modules carry stable names.
def engine_warm_step(statics, floats, ints):
    return _warm_packed(statics, floats, ints, checks=False)


def engine_apply_single(statics, floats, ints):
    return _apply_packed(statics, floats, ints, checks=False)


def engine_close_step(statics, floats, ints):
    return _close_packed(statics, floats, ints, checks=False)


warm_step = jax.jit(engine_warm_step)
apply_single = jax.jit(engine_apply_single)
close_step = jax.jit(engine_close_step)
# Sanitized variants: module-level partials give sanitize.checkified a
# stable identity to cache the checkify compile under.  user+float only:
# apply_single's padded rows are deliberately out of range for the
# mode="drop" scatters, so index_checks would false-positive by design.
_warm_step_checked = partial(_warm_packed, checks=True)
_apply_single_checked = partial(_apply_packed, checks=True)
_close_step_checked = partial(_close_packed, checks=True)
_ENGINE_ERRORS = "float|user"


def row_bucket(n: int) -> int:
    """Pad size for per-slot row channels (single-task servers): powers
    of two — a handful of compiled shapes per run."""
    return 1 << max(int(n - 1).bit_length(), 4)


class JaxStepper:
    """Host side of the jitted step: pads/buckets the per-slot
    row channels, packs each dispatch's operands into one float64 and one
    int32 buffer, and writes the packed results back into the numpy
    ``ClusterState`` mirror in place.  The static hardware arrays are
    uploaded once and reused across every dispatch of the run; only the
    dynamic columns each kernel reads and writes cross the link."""

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

    def _dispatch(self, fn, reads, writes, floats=(), ints=()):
        """One packed round trip: upload the ``reads`` columns and the
        call's own ``floats`` / ``ints`` operands as two buffers, run
        ``fn``, read both results back at once and write the ``writes``
        columns into the mirror in place.  Counts the two buffers each
        way (and the static triple on the run's first dispatch); returns
        the float64 result past the written columns."""
        st = self.state
        if self._static is None:
            with jax.enable_x64(True):
                self._static = static_arrays(st)
            obs_rt.count_transfer("h2d", "engine", lambda: self._static)
        up = tuple(
            np.concatenate([getattr(st, name).ravel() for name in names]
                           + list(operands), dtype=dtype)
            for names, operands, dtype in zip(reads, (floats, ints),
                                              (np.float64, np.int32)))
        obs_rt.count_transfer("h2d", "engine", lambda: up)
        with jax.enable_x64(True):
            down = jax.device_get(fn(self._static, *up))
        obs_rt.count_transfer("d2h", "engine", lambda: down)
        rest = []
        for names, buf in zip(writes, down):
            at = 0
            for name in names:
                col = getattr(st, name)
                col[...] = buf[at:at + col.size].reshape(col.shape)
                at += col.size
            rest.append(buf[at:])
        return rest[0]

    def progress_warming(self, slot_s: float) -> None:
        st = self.state
        if not (st.state == WARMING).any():
            return
        obs_rt.count_new_shape("engine.retrace.warm_step",
                               str(st.n_servers))
        obs_rt.count("engine.host_sync.warm_step")
        warm_fn, _, _ = self._kernels()
        self._dispatch(warm_fn, WARM_IO, WARM_IO, floats=[[slot_s]])

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
        _, apply_fn, _ = self._kernels()
        rows = self._dispatch(
            apply_fn, APPLY_IO, APPLY_IO,
            floats=[np.pad(work_raw, (0, pad))],
            ints=[np.pad(gs, (0, pad),
                         constant_values=st.n_servers),  # OOB -> dropped
                  np.pad(mids, (0, pad)),
                  np.pad(np.ones(k, np.int32), (0, pad))])
        return tuple(rows.reshape(4, bucket)[:, :k])

    def close_slot(self, slot_s: float):
        """Drain/bill the slot; returns the per-server power draw (J)
        and active mask for the host-side regional reduction."""
        st = self.state
        obs_rt.count_new_shape("engine.retrace.close_step",
                               str(st.n_servers))
        obs_rt.count("engine.host_sync.close_step")
        _, _, close_fn = self._kernels()
        power_j = self._dispatch(close_fn, CLOSE_READS, CLOSE_WRITES,
                                 floats=[[slot_s]])
        return power_j, st.active_mask()

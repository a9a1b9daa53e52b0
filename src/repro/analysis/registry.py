"""The jit-extent registry: WHICH code the hazard rules apply to.

The analyzer is repo-specific by design — the registry names the modules
whose functions execute inside (or drive) a ``jax.jit`` trace, the helper
functions that are traced despite carrying no decorator (scan bodies,
Pallas kernel bodies, shared math helpers), the documented bucketing
helpers that make host->device call shapes finite, and the pytree-view /
source-dataclass pairs whose field coverage must not drift.

Adding a new jitted module?  Add it to ``JIT_EXTENT_GLOBS`` (or the
analyzer will never look at it).  Adding a new ``ClusterState`` field?
Either mirror it in ``EngineStep`` or record it in the view's
``host_only`` table with a reason — silence is an error.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

# Modules whose code runs inside (or immediately wraps) jit traces.
# Paths are repo-relative globs over ``src/``.
JIT_EXTENT_GLOBS = (
    "src/repro/core/micro_jax.py",
    "src/repro/sim/engine_jax.py",
    "src/repro/kernels/*/kernel.py",
    "src/repro/kernels/*/ops.py",
    "src/repro/kernels/*/fused.py",
)

# Functions that are traced although they carry no @jax.jit decorator:
# helpers called from inside jitted functions or Pallas kernel bodies.
# Keyed by module basename-relative path suffix; values are function
# names.  Nested ``def``s inside traced functions are traced implicitly;
# this table covers module-level helpers.
# The ``*_impl`` bodies are traced through the named entry functions
# the production jits wrap (``micro_scan_all``, ``engine_*``) and
# through the sanitizer's checkified partials.
EXTRA_TRACED: Dict[str, Tuple[str, ...]] = {
    "src/repro/core/micro_jax.py": (
        "_entry_contrib_tail", "_entry_contribs", "_sum_newest_first",
        "_scan_assign_multi_impl"),
    "src/repro/sim/engine_jax.py": (
        "warm_step_impl", "apply_single_impl", "close_step_impl"),
}

# Host-side wrapper functions inside jit-extent modules: they build
# operands, dispatch the jitted entry and sync results — np.* use there
# is the *documented* host side, not a hazard.  Everything not listed
# here and not detected as traced is treated as host code too; this
# table exists so the traced-function discovery errs toward safety for
# ambiguous names.
HOST_WRAPPERS: Dict[str, Tuple[str, ...]] = {
    "src/repro/core/micro_jax.py": (
        "assign_scan", "assign_scan_all", "_writeback", "server_pad_map",
        "bucket", "_loc_consts", "_hw_consts", "_switch_consts",
        "_active_code"),
    "src/repro/sim/engine_jax.py": (
        "static_arrays", "row_bucket", "_model_switch_s"),
}

# The documented pad-and-mask bucketing helpers: a host wrapper that
# pads operands for a jitted entry must route the dynamic axis through
# one of these, or it is a retrace hazard (every new N compiles).
BUCKET_HELPERS = ("bucket", "row_bucket", "server_pad_map")

# Decorator spellings that mark a function as jit-compiled.
JIT_DECORATORS = ("jax.jit", "jit", "partial(jax.jit", "jax.pmap",
                  "functools.partial(jax.jit")


@dataclasses.dataclass(frozen=True)
class PytreeView:
    """A device-side pytree view paired with its host source dataclass.
    ``mirrored`` fields must exist on both; ``derived`` maps view fields
    to the source field they are computed from; ``host_only`` lists
    source fields that deliberately never reach the device, each with a
    reason.  Any source field in none of the three tables is drift."""

    view: str                       # "module:ClassName"
    source: str                     # "module:ClassName"
    derived: Dict[str, str]         # view field -> source field
    host_only: Dict[str, str]       # source field -> reason


PYTREE_VIEWS = (
    PytreeView(
        view="repro.sim.engine_jax:EngineStep",
        source="repro.sim.state:ClusterState",
        derived={"speed": "tflops"},
        host_only={
            "region_ptr": "static segment layout; regional reductions "
                          "stay host-side for parity",
            "power_price": "billing happens in the host reduction of "
                           "_finish_slot",
            "gpu_id": "hardware catalog index; never read by step math",
            "tflops": "uploaded as the derived `speed` column",
            "mem_gb": "scheduler-side eligibility input, not step state",
            "kind_id": "scheduler-side scoring input, not step state",
            "capacity": "activation-target input consumed on the host",
        },
    ),
    PytreeView(
        view="repro.core.micro_jax:DeviceRings",
        source="repro.core.micro_state:LocalityState",
        derived={},
        host_only={
            "uid": "synthesized deterministically at host export "
                   "(region_state); the scan never reads uids",
            "count": "derived from mids != EMPTY at export",
        },
    ),
)

# Kernel directories must ship a `ref.py` oracle and at least one test
# module that references the kernel package by name.
KERNELS_ROOT = "src/repro/kernels"
TESTS_ROOT = "tests"

# Retrace counters the budget enforcer knows about: every counter whose
# name starts with one of these prefixes is a retrace path and must have
# a budget entry once sighted.
RETRACE_COUNTER_PREFIXES = ("micro.retrace.", "engine.retrace.")

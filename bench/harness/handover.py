"""How each key of a configuration file reaches the program.

Every key of a configuration file (the ``torta`` group key by key, as
``torta.<name>``) is in one or more of three tables:

* ``GENERATOR_KEYS`` - read only by the benchmark's own generators and
  output check (``harness.world``, ``harness.reference``), or describing
  the file;
* ``HANDED`` - handed to the program through its public arguments: the
  per-server columns of ``ClusterState``, the per-task columns of
  ``TaskBatch``, and the arguments of ``TortaScheduler`` and ``Engine``;
* ``PINNED`` - numbers the program states itself and takes no argument
  for.  Each entry reads the program's own value (a public name or a
  default argument; for a literal in the program's code, the value as
  that code states it) and names where the program reads it.

``refusals`` lists what a configuration states that the program would not
run: a key in no table, a handed or pinned key the file leaves out, and a
pinned number that differs from the program's.  ``program.program_world``
raises ``Refused`` with that list before it builds anything, so a file
cannot state a number that the program silently replaces with its own.
"""
from __future__ import annotations

import dataclasses
import importlib
import inspect
from typing import Callable, Dict, List, Mapping

GENERATOR_KEYS = ("name", "source", "assumed", "topology", "fleet",
                  "servers_per_region", "warmup_slots",
                  "reference_task_work_s", "precision", "reference")

# key: where the program takes it
HANDED: Dict[str, str] = {
    "gpu_types": "ClusterState columns tflops, mem_gb, power_w, kind_id, "
                 "capacity, switch_scale (harness.world.make_fleet); the "
                 "names label rows only",
    "models": "TaskBatch columns work_s, mem_gb, kind_id "
              "(harness.world.Traffic); the names are pinned",
    "slot_seconds": "Engine(slot_seconds=)",
    "drop_after_slots": "Engine(drop_after_slots=)",
    "torta.eta": "TortaScheduler(eta=)",
    "torta.sigma": "TortaScheduler(sigma=)",
    "torta.headroom": "TortaScheduler(headroom=)",
    "torta.scheduler_seed": "TortaScheduler(seed=)",
    "torta.micro_backend": "TortaScheduler(micro_backend=)",
    "torta.step_backend": "Engine(step_backend=)",
}


@dataclasses.dataclass(frozen=True)
class Pin:
    """A number the program states itself: ``program()`` reads it,
    ``where`` says where the program uses it, ``part`` takes from the
    file's value what is compared with it."""

    program: Callable[[], object]
    where: str
    part: Callable[[object], object] = lambda value: value


def _name(module: str, path: str) -> Callable[[], object]:
    """The program's ``module.path`` (``path`` may be dotted)."""
    def read():
        obj = importlib.import_module(module)
        for attr in path.split("."):
            obj = getattr(obj, attr)
        return obj
    return read


def _default(module: str, attr: str, param: str) -> Callable[[], object]:
    def read():
        return inspect.signature(_name(module, attr)()).parameters[
            param].default
    return read


def _literal(value) -> Callable[[], object]:
    return lambda: value


def _names(table: dict) -> list:
    return [k for k in table if not k.startswith("_")]


_MICRO = "repro.core.micro"
PINNED: Dict[str, Pin] = {
    "models": Pin(_name("repro.sim.state", "MODEL_NAMES"),
                  "core/micro._MODEL_RANK ranks urgency ties by model name "
                  "over the program's catalog", _names),
    "kinds": Pin(_name(_MICRO, "KIND_ORDER"),
                 "core/micro._DEMAND_BY_KIND, indexed by kind_id"),
    "kind_demand_tflops": Pin(_name(_MICRO, "DEMAND_TFLOPS"),
                              "core/micro._DEMAND_BY_KIND (Eq 8)"),
    "switch_stages_s": Pin(_name("repro.sim.cluster", "SWITCH_STAGES_S"),
                           "sim/cluster.MODEL_SWITCH_S, sim/state._WARM_HIT_S"
                           " (engine, micro, jitted step)"),
    "cold_start_s": Pin(_name("repro.sim.cluster", "COLD_START_S"),
                        "sim/engine.py, servers woken by Eq 6"),
    "switch_power_frac": Pin(_name("repro.sim.cluster", "SWITCH_POWER_FRAC"),
                             "sim/engine.py, sim/engine_jax.py"),
    "warm_models_kept": Pin(_name("repro.sim.state", "WARM_SLOTS"),
                            "sim/engine_jax.py, the MRU warm list"),
    "reference_speed_tflops": Pin(_literal(112.0),
                                  "the literal 112.0: sim/engine.py, "
                                  "sim/engine_jax.py, core/micro.py, "
                                  "core/micro_jax.py"),
    "torta.sinkhorn_reg": Pin(_name("repro.core.macro", "MacroAllocator.reg"),
                              "core/macro.MacroAllocator.reg"),
    "torta.sinkhorn_iters": Pin(_default("repro.core.ot", "sinkhorn",
                                         "n_iters"),
                                "core/ot.sinkhorn(n_iters=)"),
    "torta.cost_w_power": Pin(_default("repro.core.ot", "cost_matrix", "w1"),
                              "core/ot.cost_matrix(w1=)"),
    "torta.cost_w_latency": Pin(_default("repro.core.ot", "cost_matrix",
                                         "w2"),
                                "core/ot.cost_matrix(w2=)"),
    "torta.ema_alpha": Pin(_default("repro.core.predictor", "EmaPredictor",
                                    "alpha"),
                           "core/predictor.EmaPredictor(alpha=)"),
    "torta.shock_l1": Pin(_literal(0.25), "the literal 0.25: core/macro.py"),
    "torta.w_hw": Pin(_name(_MICRO, "W_HW"), "core/micro.W_HW (Eq 7)"),
    "torta.w_load": Pin(_name(_MICRO, "W_LOAD"), "core/micro.W_LOAD (Eq 7)"),
    "torta.w_loc": Pin(_name(_MICRO, "W_LOC"), "core/micro.W_LOC (Eq 7)"),
    "torta.w_warm": Pin(_name(_MICRO, "W_WARM"), "core/micro.W_WARM"),
    "torta.warm_cache_bonus": Pin(_literal(0.4),
                                  "the literal 0.4: core/micro.py, "
                                  "core/micro_jax.py"),
    "torta.w_model": Pin(_name(_MICRO, "W_MODEL"),
                         "core/micro.W_MODEL (Eq 10)"),
    "torta.w_embed": Pin(_name(_MICRO, "W_EMBED"),
                         "core/micro.W_EMBED (Eq 10)"),
    "torta.loc_decay": Pin(_name(_MICRO, "LOC_DECAY"),
                           "core/micro.LOC_DECAY (Eq 10)"),
    "torta.loc_max_age": Pin(_literal(40),
                             "the literal 40: core/micro.py, "
                             "core/micro_jax.py, core/micro_state.py"),
    "torta.history_keep": Pin(_name(_MICRO, "MicroAllocator.KEEP"),
                              "core/micro.MicroAllocator.KEEP"),
    "torta.queue_cap_slots": Pin(_literal(16.0),
                                 "the literal 16.0: core/micro.py, "
                                 "core/micro_jax.py"),
    "torta.exec_penalty": Pin(_literal(0.3),
                              "the literal 0.3: core/micro.py, "
                              "core/micro_jax.py"),
    "torta.wait_lin": Pin(_literal(0.8), "the literal 0.8: core/micro.py, "
                                         "core/micro_jax.py"),
    "torta.wait_quad": Pin(_literal(0.4), "the literal 0.4: core/micro.py, "
                                          "core/micro_jax.py"),
}


class Refused(ValueError):
    """The configuration states what the program would not run."""


def file_keys(cfg: Mapping) -> List[str]:
    """The file's keys, the ``torta`` group key by key."""
    keys = [k for k in cfg if k != "torta"]
    return keys + [f"torta.{k}" for k in cfg.get("torta", {})]


def _value(cfg: Mapping, key: str):
    group, _, sub = key.partition(".")
    return cfg[group][sub] if sub else cfg[group]


def _plain(x):
    """JSON's shapes: tuples as lists, mapping values alike."""
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    if isinstance(x, Mapping):
        return {k: _plain(v) for k, v in x.items()}
    return x


def refusals(cfg: Mapping) -> List[str]:
    """One line per key that the program would not run as the file
    states it (empty when it runs the whole file)."""
    known = set(GENERATOR_KEYS) | set(HANDED) | set(PINNED)
    keys = file_keys(cfg)
    out = [f"{k}: the harness hands this key to nothing" for k in keys
           if k not in known]
    out += [f"{k}: missing; the program needs it" for k in
            dict.fromkeys([*HANDED, *PINNED]) if k not in keys]
    for key, pin in PINNED.items():
        if key not in keys:
            continue
        stated = _plain(pin.part(_value(cfg, key)))
        own = _plain(pin.program())
        if stated != own:
            out.append(f"{key}: the file states {stated!r}; the program "
                       f"takes no such argument and uses {own!r} "
                       f"({pin.where})")
    return out


def refuse(cfg: Mapping) -> None:
    """Raise ``Refused`` naming every line of ``refusals``."""
    lines = refusals(cfg)
    if lines:
        raise Refused(f"configuration {cfg.get('name')!r}: "
                      + "; ".join(lines))

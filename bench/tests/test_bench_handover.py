"""How a configuration file reaches the program (``harness.handover``) and
which reference checks it (``manifest.reference_class``), on the CPU at
the tiny size.

* Every key of the accepted configuration files is handed to the
  program, pinned to the program's own value, or read by the benchmark's
  generators alone.
* A tiny configuration with a GPU table of its own runs ``correct``; one
  that also brings models and constants of its own is refused at set-up,
  naming each key; and the output check holds a program that ran at its
  own constants to the file's, one pinned key at a time.
* A configuration that names its own copy of the reference is checked by
  that copy.
"""
import copy
import json
import time

import bench_testkit as kit
import pytest

from harness import check, control, handover, runner, world
from harness.manifest import reference_class

CONFIGS = ("paper-gabriel", "paper-cost2")

# a value of its own for every pinned key, each far enough from the
# paper's to change what the reference computes
OWN = {
    "models": None,                                  # see own_models
    "kinds": ["memory", "compute", "lightweight"],
    "kind_demand_tflops": {"compute": 400.0, "memory": 50.0,
                           "lightweight": 20.0},
    "switch_stages_s": {"unload": 7.0, "cleanup": 1.0, "load": 60.0,
                        "init": 20.0, "reconfig": 1.0},
    "cold_start_s": 20.0,
    "switch_power_frac": 0.5,
    "warm_models_kept": 1,
    "reference_speed_tflops": 60.0,
    "torta.sinkhorn_reg": 0.5,
    "torta.sinkhorn_iters": 0,
    "torta.cost_w_power": 0.1,
    "torta.cost_w_latency": 1.0,
    "torta.ema_alpha": 0.9,
    "torta.shock_l1": 1e-4,
    "torta.w_hw": 2.0,
    "torta.w_load": 0.01,
    "torta.w_loc": 3.0,
    "torta.w_warm": 0.0,
    "torta.warm_cache_bonus": 1.5,
    "torta.w_model": 0.05,
    "torta.w_embed": 2.0,
    "torta.loc_decay": 0.01,
    "torta.loc_max_age": 1,
    "torta.history_keep": 1,
    "torta.queue_cap_slots": 0.02,
    "torta.exec_penalty": 5.0,
    "torta.wait_lin": 10.0,
    "torta.wait_quad": 10.0,
}

# pinned keys that no run of the tiny cell can show (PERF.md, "How a
# configuration reaches the program"): no server is woken, so the cold
# start is never paid; the power term of the transport cost is the same
# in every row, so the column potentials absorb it and no value moves a
# converged plan
UNREACHED = ("cold_start_s", "torta.cost_w_power")


def own_models(cfg: dict) -> dict:
    """Seven models: the paper's six under names that sort another way,
    and one that takes most of the largest GPU's memory."""
    models = {"_columns": cfg["models"]["_columns"]}
    for i, name in enumerate(world.model_table(cfg)[0]):
        models[f"{chr(ord('z') - i)}-{name}"] = cfg["models"][name]
    models["big-moe-shard"] = [37.0, 72, "memory"]
    return models


def own_gpus(cfg: dict) -> dict:
    """Six GPU types: the paper's five with rows of their own, and one
    under a new name."""
    gpus = {"_columns": cfg["gpu_types"]["_columns"]}
    for name in world.gpu_table(cfg)[0]:
        tf, mem, watts, kind, (c_lo, c_hi), scale = cfg["gpu_types"][name]
        gpus[name] = [tf * 1.25, mem, watts * 0.8, kind,
                      [c_lo * 1.25, c_hi * 1.25], scale * 1.1]
    gpus["L40S"] = [362.0, 48, 350, "lightweight", [14.0, 20.0], 0.5]
    return gpus


def with_value(cfg: dict, key: str, new) -> dict:
    out = copy.deepcopy(cfg)
    group, _, sub = key.partition(".")
    if sub:
        out[group][sub] = new
    else:
        out[group] = new
    return out


def own_value(cfg: dict, key: str):
    return own_models(cfg) if key == "models" else OWN[key]


def write_tiny(root, cfg: dict) -> None:
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return kit.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def paper_run(root):
    """One tiny run of the program, which runs at its own constants."""
    return kit.run_tiny(root, seed=2 ** 31 + 29)


@pytest.fixture(autouse=True)
def fewer_scored_tasks(monkeypatch):
    monkeypatch.setattr(check, "CHECK_TASKS", 3000)


# ------------------------------------------------------ (a) key coverage


@pytest.mark.parametrize("name", CONFIGS)
def test_every_key_reaches_the_program_or_the_generators(name):
    cfg = json.loads((kit.BENCH / "configs" / f"{name}.json").read_text())
    keys = handover.file_keys(cfg)
    known = (set(handover.GENERATOR_KEYS) | set(handover.HANDED)
             | set(handover.PINNED))
    assert [k for k in keys if k not in known] == []
    assert set(handover.HANDED) | set(handover.PINNED) <= set(keys)
    assert handover.refusals(cfg) == []


def test_no_key_is_both_generator_only_and_the_programs():
    assert not set(handover.GENERATOR_KEYS) & (set(handover.HANDED)
                                               | set(handover.PINNED))
    assert set(OWN) == set(handover.PINNED)


@pytest.mark.parametrize("case", ("unknown", "missing", "missing_torta"))
def test_a_key_the_program_would_not_run_is_named(case):
    cfg = json.loads((kit.BENCH / "configs" / "paper-gabriel.json")
                     .read_text())
    if case == "unknown":
        cfg["prefill_chunk"], key = 512, "prefill_chunk"
    elif case == "missing":
        del cfg["cold_start_s"]
        key = "cold_start_s"
    else:
        del cfg["torta"]["eta"]
        key = "torta.eta"
    lines = handover.refusals(cfg)
    assert [line.split(":")[0] for line in lines] == [key]
    with pytest.raises(handover.Refused, match=key):
        handover.refuse(cfg)


# --------------------------------- (b) a configuration of its own, tiny


def test_a_gpu_table_of_its_own_runs_correct(tmp_path):
    root = kit.make_root(tmp_path)
    cfg = json.loads((root / "bench" / "configs" / "tiny.json").read_text())
    cfg["gpu_types"] = own_gpus(cfg)
    write_tiny(root, cfg)
    cfg, fleet, latency, run = kit.run_tiny(root, seed=2 ** 31 + 31)
    assert int(fleet.gpu_id.max()) == 5       # the new GPU type is in it
    readings = check.replay(cfg, fleet, latency, run, seed=31)
    assert readings.correct, readings.lines()


def test_models_and_constants_of_its_own_are_refused_at_set_up(tmp_path):
    root = kit.make_root(tmp_path)
    cfg = json.loads((root / "bench" / "configs" / "tiny.json").read_text())
    cfg["gpu_types"] = own_gpus(cfg)
    for key in handover.PINNED:
        cfg = with_value(cfg, key, own_value(cfg, key))
    assert sorted(line.split(":")[0] for line in handover.refusals(cfg)) \
        == sorted(handover.PINNED)
    write_tiny(root, cfg)
    with pytest.raises(handover.Refused) as err:
        kit.run_tiny(root, seed=2 ** 31 + 37)
    for key in handover.PINNED:
        assert f"{key}:" in str(err.value)


@pytest.mark.parametrize("key", [k for k in handover.PINNED
                                 if k not in UNREACHED])
def test_the_check_holds_the_program_to_the_file(paper_run, key):
    """The program ran at its own constants; against a file that states
    another value of one pinned key, the check reads not correct."""
    cfg, fleet, latency, run = paper_run
    own = with_value(cfg, key, own_value(cfg, key))
    readings = check.replay(own, fleet, latency, run, seed=29)
    assert not readings.correct, readings.lines()


def test_the_paper_run_reads_correct(paper_run):
    cfg, fleet, latency, run = paper_run
    readings = check.replay(cfg, fleet, latency, run, seed=29)
    assert readings.correct, readings.lines()


def test_a_refused_configuration_prints_no_result(tmp_path, capsys,
                                                  monkeypatch):
    import jax
    root = kit.make_root(tmp_path)
    cfg = json.loads((root / "bench" / "configs" / "tiny.json").read_text())
    write_tiny(root, with_value(cfg, "torta.w_hw", 0.5))
    monkeypatch.setattr(runner, "compile_cache_dir", lambda _root: "off")
    rc = runner.run_cell(root, kit.TINY, 2 ** 31 + 41, 1.0, False,
                         time.perf_counter(), jax.devices()[0])
    out, err = capsys.readouterr()
    assert rc == 5
    assert "torta.w_hw" in err
    assert '"correct"' not in out


# ---------------------------------------------------- (c) named reference


def named_copy(root, name: str, old: str, new: str) -> str:
    """A copy of the shared reference under the checkout's ``bench/``
    with ``old`` replaced by ``new``; returns its name for the file."""
    text = (kit.BENCH / "harness" / "reference.py").read_text()
    assert text.count(old) == 1
    path = root / "bench" / "refs" / f"{name}.py"
    path.parent.mkdir(exist_ok=True)
    path.write_text(text.replace(old, new))
    return f"bench/refs/{name}.py"


def test_a_named_reference_is_the_one_that_checks(root, paper_run):
    cfg, fleet, latency, run = paper_run
    faulty = named_copy(root, "faulty",
                        "(f(0.1) + f(0.9) * self.util)",
                        "(f(0.1) + f(0.8) * self.util)")
    named = dict(cfg, reference=faulty)
    assert handover.refusals(named) == []
    readings = check.replay(named, fleet, latency, run, seed=29, root=root)
    assert not readings.correct
    assert readings.values["outcome_rel"] > check.LIMITS["outcome_rel"]
    same = named_copy(root, "same", "(f(0.1) + f(0.9) * self.util)",
                      "(f(0.1) + f(0.9) * self.util)")
    readings = check.replay(dict(cfg, reference=same), fleet, latency, run,
                            seed=29, root=root)
    assert readings.correct, readings.lines()


def test_the_control_runs_the_named_reference(root):
    marked = named_copy(root, "marked", '        self.cfg = cfg\n',
                        '        raise LookupError("the named copy")\n')
    cfg, fleet, latency, _, traffic = kit.world_of(root, kit.TINY, 43)
    with pytest.raises(LookupError, match="the named copy"):
        control.run_control(dict(cfg, reference=marked), traffic, fleet,
                            latency, root=root)


@pytest.mark.parametrize("name", ("../reference.py", "bench/refs/x.json",
                                  "src/repro/sim/reference.py"))
def test_a_reference_outside_the_benchmark_is_refused(root, name):
    with pytest.raises(ValueError, match="not a .py file under bench/"):
        reference_class({"name": "x", "reference": name}, root)

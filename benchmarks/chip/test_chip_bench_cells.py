"""Every cell of ``BENCHMARK.json`` resolves, by name, to its configuration,
model family, traffic mix, limits and metric readers; the files keep to the
benchmark's format; at a tiny size on the CPU, the comparison that decides
``correct`` passes the program at the configuration's precision and fails
the same reference run in bfloat16; and a new model family enters as new
files alone."""
import json
import os
import re
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import harness  # noqa: E402

BENCH = harness.load_benchmark(ROOT)
CELLS = [c["name"] for c in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_resolves_to_its_files(cell_name):
    cell, config, traffic, limits = harness.resolve_cell(BENCH, cell_name,
                                                         ROOT)
    assert len(cell["why"]) <= 200 and cell["chips"] in (1, 4)
    entry = {c["name"]: c for c in BENCH["configs"]}[cell["config"]]
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    for key in entry["reduced"]:
        assert key in config and key in config["published"]
        # no width: hidden, intermediate or head sizes, *_dim, *_rank
        assert key == "vocab_size" or not key.endswith(
            ("_size", "_dim", "_rank"))
        assert key not in ("num_experts_per_tok", "num_attention_heads",
                           "num_key_value_heads")
    harness.policy_kwargs(config, traffic)
    harness.check_prefill_fits(traffic)
    assert limits["limits"] and all(v >= 0 for v in limits["limits"].values())
    e2e = harness.cell_metrics(BENCH, cell_name, "end_to_end")
    per_layer = harness.cell_metrics(BENCH, cell_name, "per_layer")
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert per_layer
    for m in per_layer:
        assert m["moves"] in [e["name"] for e in e2e]
        assert callable(harness.load_reader(m["name"]))


READERS = sorted(f[:-3] for f in os.listdir(os.path.join(HERE, "metrics"))
                 if f.endswith(".py"))


FAMILY_EXPORTS = ("arch_from_config", "policy_fields", "make_params",
                  "q_values", "last_q", "train_three", "sgd_step",
                  "learner_step_flops")


@pytest.mark.parametrize("config_name",
                         [c["name"] for c in BENCH["configs"]])
def test_configuration_names_an_existing_family(config_name):
    entry = {c["name"]: c for c in BENCH["configs"]}[config_name]
    config = harness.load_json(os.path.join(ROOT, entry["file"]))
    assert NAME.match(config["family"])
    assert os.path.isfile(os.path.join(HERE, "families",
                                       f"{config['family']}.py"))
    family = harness.load_family(config["family"])
    assert all(callable(getattr(family, n)) for n in FAMILY_EXPORTS)
    arch = family.arch_from_config(config)
    assert arch.obs_dim == int(np.prod(config["observation_shape"]))
    assert arch.num_actions >= 2 and arch.window >= 1
    assert set(family.policy_fields(config)) >= {"num_layers", "window"}


def test_every_per_layer_metric_has_a_reader():
    assert {m["name"] for m in BENCH["per_layer"]} <= set(READERS)


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_without_a_reading(name):
    ctx = {"peak": None, "trace": {"op_seconds": {}, "idle_share": None},
           "medians": {}, "steps_in_window": 0, "window_s": 1.0,
           "before": {"learner_steps": 0, "learner_walltime": 0.0,
                      "batches": 0, "prefill_rows": 0, "decode_rows": 0},
           "traffic": json.load(open(os.path.join(HERE, "traffic",
                                                  "r2d2.json")))}
    ctx["after"] = ctx["before"]
    assert harness.load_reader(name)(ctx) is None, name


def _histogram(count, total):
    return ({"type": "histogram", "count": 0} if count == 0 else
            {"type": "histogram", "count": count, "sum": total,
             "mean": total / count})


# the registry at the window's two ends, as a traced run snapshots it: a
# histogram that had seen nothing carries no sum, and the priority span is
# first recorded inside the window
TELEMETRY_BEFORE = {
    "learner/input_ms": _histogram(10, 20.0),
    "learner/metrics_ms": _histogram(10, 15.0),
    "inference/batch_ms": _histogram(0, 0.0),
    "engine/param_bytes_to_device": {"type": "counter", "value": 4 * 10**6},
}
TELEMETRY_AFTER = {
    "learner/input_ms": _histogram(14, 26.0),
    "learner/metrics_ms": _histogram(14, 21.0),
    "learner/priorities_ms": _histogram(4, 10.0),
    "inference/batch_ms": _histogram(5, 1200.0),
    "engine/param_bytes_to_device": {"type": "counter",
                                     "value": 4 * 10**6 + 6_925_434_880},
}


@pytest.mark.parametrize("name,expected", [
    ("learner_input_ms", 6.0 / 4),
    ("learner_host_ms", (6.0 + 10.0) / 4),
    ("serve_batch_ms", 1200.0 / 5),
    ("serve_param_bytes_per_batch", 1385.086976),
])
def test_telemetry_reader_takes_window_differences(name, expected):
    ctx = {"before": {"telemetry": TELEMETRY_BEFORE},
           "after": {"telemetry": TELEMETRY_AFTER}}
    assert harness.load_reader(name)(ctx) == pytest.approx(expected)


def test_snapshot_takes_the_registry_only_when_traced():
    from types import SimpleNamespace

    from repro.telemetry import registry
    learner = SimpleNamespace(learner_walltime=1.5)
    server = SimpleNamespace(stats=lambda: {"batches": 3, "prefill_rows": 2,
                                            "decode_rows": 4})
    builder = SimpleNamespace(record=SimpleNamespace(step_ends=[1, 2]))
    registry.configure(enabled=True)
    try:
        registry.counter("engine/param_bytes_to_device").inc(7)
        for ms in (1.0, 3.0):
            registry.histogram("learner/input_ms").observe(ms)
        untraced = harness._snapshot(learner, server, builder, False)
        traced = harness._snapshot(learner, server, builder, True)
    finally:
        registry.unconfigure()
    assert "telemetry" not in untraced
    assert {k: v for k, v in traced.items() if k != "telemetry"} == untraced
    telemetry = traced["telemetry"]
    assert telemetry["engine/param_bytes_to_device"]["value"] == 7
    assert telemetry["learner/input_ms"]["count"] == 2
    assert telemetry["learner/input_ms"]["sum"] == 4.0
    assert "reservoir" not in telemetry["learner/input_ms"]


def test_seeds_beyond_32_bits_derive_small_seeds():
    seeds = harness.derive_seeds(2 ** 31 + 12345)
    assert seeds == harness.derive_seeds(2 ** 31 + 12345)
    assert all(0 <= s < 2 ** 30 for s in seeds.values())
    assert seeds != harness.derive_seeds(2 ** 31 + 12346)
    assert np.random.RandomState(seeds["prefill"] + 8000) is not None


TINY_WIDTHS = os.path.join(HERE, "testdata", "tiny-widths.json")


def tiny_config(cell_name):
    """The cell's configuration at tiny widths; depth, window and
    observations as the cell has them."""
    _, config, _, _ = harness.resolve_cell(BENCH, cell_name, ROOT)
    config.update(harness.load_json(TINY_WIDTHS))
    return config


def learner_run(cell_name, log_dir):
    """The program's learner at a tiny size on the CPU, three steps from the
    benchmark's weights, recorded as a run records them."""
    import jax

    from bench_builder import BuilderFactory, CatchFactory
    from repro.core import make_environment_spec

    config = tiny_config(cell_name)
    family = harness.load_family(config["family"])
    _, _, traffic, limits = harness.resolve_cell(BENCH, cell_name, ROOT)
    traffic["learner"].update(batch_size=8, sequence_length=16, period=8,
                              min_replay_size=64)
    traffic["prefill_sequences"] = 16
    seeds = harness.derive_seeds(2 ** 31 + 7)
    arch = family.arch_from_config(config)
    env_factory = CatchFactory(*config["observation_shape"])
    factory = BuilderFactory(harness.policy_kwargs(config, traffic),
                             seeds["program"], log_dir, False)
    builder = factory(make_environment_spec(env_factory(0)))
    table = builder.make_replay()
    harness._prefill(builder, table, traffic, seeds["prefill"], env_factory)
    learner = builder.make_learner(builder.make_dataset(table),
                                   priority_update_cb=table.update_priorities)
    params = family.make_params(seeds["weights"], arch)
    learner.state = learner.state._replace(
        params=params, target_params=jax.tree.map(jax.numpy.copy, params))
    builder.record.release.set()
    for _ in range(3):
        learner.step()
    record = builder.record
    recorded = {"batches": record.batches, "losses": record.losses,
                "priorities": record.priorities,
                "first_moment": record.first_moment,
                "params_after": record.params_after}
    return family, arch, traffic, seeds, recorded, limits["limits"]


@pytest.fixture(scope="module", params=CELLS)
def tiny_learner_run(request, tmp_path_factory):
    return learner_run(request.param, str(tmp_path_factory.mktemp("logs")))


def assert_comparison_passes_the_program_and_fails_bfloat16(run):
    import checks
    family, arch, traffic, seeds, recorded, limits = run
    limits = {n: v for n, v in limits.items() if not n.startswith("serve")}
    numbers, extra = checks.check_learner(family, arch, traffic["learner"],
                                          seeds["weights"], recorded,
                                          controls=True)
    assert set(limits) <= set(numbers)
    assert all(numbers[n] <= limits[n] for n in limits), numbers
    assert any(extra[f"control.{n}"] > limits[n] for n in limits), extra
    assert any(extra[f"fault.half_batch.{n}"] > limits[n] for n in limits)


def test_comparison_passes_the_program_and_fails_bfloat16(tiny_learner_run):
    assert_comparison_passes_the_program_and_fails_bfloat16(tiny_learner_run)


def assert_serve_check_catches_an_altered_action(cell_name):
    import jax.numpy as jnp

    import checks
    config = tiny_config(cell_name)
    family = harness.load_family(config["family"])
    arch = family.arch_from_config(config)
    rng = np.random.RandomState(0)
    n = 64
    windows = (rng.rand(n, arch.window, arch.obs_dim) > 0.9).astype(
        np.float32)
    positions = rng.randint(0, 12, n).astype(np.int32)
    lengths = np.minimum(positions + 1, arch.window)
    q = np.asarray(family.last_q(family.make_params(5, arch), arch,
                                 jnp.asarray(windows),
                                 jnp.asarray(lengths, jnp.int32)))
    requests = {"window": windows, "position": positions,
                "action": q.argmax(-1).astype(np.int32),
                "ok": np.ones(n, bool), "req_of_row": np.arange(n)}
    numbers, _ = checks.check_serve(family, arch, 5, requests)
    assert numbers["serve_gap"] == 0.0
    requests["action"] = (requests["action"] + 1) % arch.num_actions
    numbers, _ = checks.check_serve(family, arch, 5, requests)
    assert numbers["serve_gap"] > 0.1


def test_serve_check_catches_an_altered_action():
    assert_serve_check_catches_an_altered_action(CELLS[0])


TWIN_FAMILY = '''"""The dense family under another name."""
import harness

_dense = harness.load_family("dense")
arch_from_config = _dense.arch_from_config
policy_fields = _dense.policy_fields
make_params = _dense.make_params
q_values = _dense.q_values
last_q = _dense.last_q
train_three = _dense.train_three
sgd_step = _dense.sgd_step
learner_step_flops = _dense.learner_step_flops
'''

TWIN_RUN = '''
import os, sys
sys.path[:0] = [os.path.join(os.getcwd(), "benchmarks", "chip")]
import test_chip_bench_cells as cells
run = cells.learner_run("twin.r2d2", os.path.join(os.getcwd(), "logs"))
print("family", os.path.relpath(run[0].__file__))
cells.assert_comparison_passes_the_program_and_fails_bfloat16(run)
cells.assert_serve_check_catches_an_altered_action("twin.r2d2")
print("twin ok")
'''


def test_a_new_family_enters_as_new_files(tmp_path):
    """A copy of the benchmark takes a second family, ``twin`` (the dense
    one re-exported), through new files alone: the family module, a
    configuration at tiny widths naming it, the cell's limits, and a
    ``BENCHMARK.json`` that lists the configuration and a cell of it.  The
    copy's own harness, comparison and tests then run the learner and serve
    comparisons through it; no copied file is edited."""
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    copied = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    (chip / "families" / "twin.py").write_text(TWIN_FAMILY)
    source = [c for c in BENCH["configs"]
              if c["name"] == BENCH["workloads"][0]["config"]][0]
    config = harness.load_json(os.path.join(ROOT, source["file"]))
    config.update(harness.load_json(TINY_WIDTHS), name="tiny-twin",
                  family="twin")
    (chip / "configs" / "tiny-twin.json").write_text(json.dumps(config))
    shutil.copy(os.path.join(HERE, "limits",
                             f"{BENCH['workloads'][0]['name']}.json"),
                chip / "limits" / "twin.r2d2.json")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(source, name="tiny-twin",
                                 file="benchmarks/chip/configs/"
                                      "tiny-twin.json"))
    bench["workloads"].append(dict(BENCH["workloads"][0], name="twin.r2d2",
                                   config="tiny-twin"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    done = subprocess.run([sys.executable, "-c", textwrap.dedent(TWIN_RUN)],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    assert "family benchmarks/chip/families/twin.py" in done.stdout
    assert "twin ok" in done.stdout
    assert all(p.read_bytes() == data for p, data in copied.items())


@pytest.mark.parametrize("program,expected", [
    ({1: 1.0, 2: 2.0, 3: 3.0}, 0.0),
    ({1: 1.0, 2: 2.0, 3: 3.3}, 0.1),     # 0.3 over the reference's 3
    ({1: 1.1, 2: 2.0, 3: 3.0}, 0.05),    # 0.1 over the median 2
    ({1: 1.0, 2: 2.0}, 1.0),             # key 3 sent no priority
])
def test_priority_gap_takes_the_worst_sequence(program, expected):
    import checks
    gap = checks.prio_gap(program, np.array([1, 2, 3]), [1.0, 2.0, 3.0])
    assert gap == pytest.approx(expected)


def test_priorities_never_sent_are_counted_missing():
    import checks
    ref = {"losses": [], "grad": {"a": 1.0}, "change": {}, "moved": set(),
           "priorities": [(np.array([1, 2]), [1.0, 2.0]),
                          (np.array([3, 4]), [1.0, 2.0])]}
    numbers = checks._learner_numbers([], {"a": 1.0}, {}, [{1: 1.0}], ref)
    assert numbers["prio_missing"] == 3
    assert numbers["prio_2"] == pytest.approx(1.0)

"""Every cell of ``BENCHMARK.json`` resolves, by name, to its configuration,
traffic mix, limits and metric readers; the files keep to the benchmark's
format; and, at a tiny size on the CPU, the comparison that decides
``correct`` passes the program at the configuration's precision and fails
the same reference run in bfloat16."""
import json
import os
import re
import sys

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


def test_every_per_layer_metric_has_a_reader():
    assert {m["name"] for m in BENCH["per_layer"]} <= set(READERS)


def test_readers_return_nothing_without_a_reading():
    ctx = {"peak": None, "trace": {"op_seconds": {}, "idle_share": None},
           "medians": {}, "steps_in_window": 0, "window_s": 1.0,
           "before": {"learner_steps": 0, "learner_walltime": 0.0,
                      "batches": 0, "prefill_rows": 0, "decode_rows": 0},
           "traffic": json.load(open(os.path.join(HERE, "traffic",
                                                  "r2d2.json")))}
    ctx["after"] = ctx["before"]
    for name in READERS:
        assert harness.load_reader(name)(ctx) is None, name


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


@pytest.fixture(scope="module", params=CELLS)
def tiny_learner_run(request, tmp_path_factory):
    """The program's learner at a tiny size on the CPU, three steps from the
    benchmark's weights, recorded as a run records them."""
    import jax

    import reference
    from bench_builder import BuilderFactory, CatchFactory
    from repro.core import make_environment_spec

    config = tiny_config(request.param)
    _, _, traffic, limits = harness.resolve_cell(BENCH, request.param, ROOT)
    traffic["learner"].update(batch_size=8, sequence_length=16, period=8,
                              min_replay_size=64)
    traffic["prefill_sequences"] = 16
    seeds = harness.derive_seeds(2 ** 31 + 7)
    arch = reference.arch_from_config(config)
    env_factory = CatchFactory(*config["observation_shape"])
    factory = BuilderFactory(harness.policy_kwargs(config, traffic),
                             seeds["program"],
                             str(tmp_path_factory.mktemp("logs")), False)
    builder = factory(make_environment_spec(env_factory(0)))
    table = builder.make_replay()
    harness._prefill(builder, table, traffic, seeds["prefill"], env_factory)
    learner = builder.make_learner(builder.make_dataset(table),
                                   priority_update_cb=table.update_priorities)
    params = reference.make_params(seeds["weights"], arch)
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
    return arch, traffic, seeds, recorded, limits["limits"]


def test_comparison_passes_the_program_and_fails_bfloat16(tiny_learner_run):
    import checks
    arch, traffic, seeds, recorded, limits = tiny_learner_run
    limits = {n: v for n, v in limits.items() if not n.startswith("serve")}
    numbers, extra = checks.check_learner(arch, traffic["learner"],
                                          seeds["weights"], recorded,
                                          controls=True)
    assert set(limits) <= set(numbers)
    assert all(numbers[n] <= limits[n] for n in limits), numbers
    assert any(extra[f"control.{n}"] > limits[n] for n in limits), extra
    assert any(extra[f"fault.half_batch.{n}"] > limits[n] for n in limits)


def test_serve_check_catches_an_altered_action():
    import jax.numpy as jnp

    import checks
    import reference
    arch = reference.arch_from_config(tiny_config(CELLS[0]))
    rng = np.random.RandomState(0)
    n = 64
    windows = (rng.rand(n, arch.window, arch.obs_dim) > 0.9).astype(
        np.float32)
    positions = rng.randint(0, 12, n).astype(np.int32)
    lengths = np.minimum(positions + 1, arch.window)
    q = np.asarray(reference.last_q(reference.make_params(5, arch), arch,
                                    jnp.asarray(windows),
                                    jnp.asarray(lengths, jnp.int32)))
    requests = {"window": windows, "position": positions,
                "action": q.argmax(-1).astype(np.int32),
                "ok": np.ones(n, bool), "req_of_row": np.arange(n)}
    numbers, _ = checks.check_serve(arch, 5, requests)
    assert numbers["serve_gap"] == 0.0
    requests["action"] = (requests["action"] + 1) % arch.num_actions
    numbers, _ = checks.check_serve(arch, 5, requests)
    assert numbers["serve_gap"] > 0.1


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

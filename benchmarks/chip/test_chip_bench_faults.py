"""The harness end to end on the CPU at a tiny size, without the look for a
chip: a sound program comes out ``correct``, and the program broken
underneath comes out not ``correct``, once for each fault the cells can
have: a learner step that returns its state unchanged, half of the batch
left out, the mean taken over the rest, and a served action altered where
the engine produces it.  One chip has no exchange between chips to leave
out.  The limits are the cells' own.  A traced run hands every per-layer
reader that counts host work the program's telemetry."""
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import harness  # noqa: E402

BENCH = harness.load_benchmark(ROOT)


def _run(cell_name, seed=3, trace=False):
    cell, config, traffic, limits = harness.resolve_cell(BENCH, cell_name,
                                                         ROOT)
    config.update(harness.load_json(os.path.join(HERE, "testdata",
                                                 "tiny-widths.json")))
    learner = traffic["learner"]
    learner.update(batch_size=8, sequence_length=16, period=8,
                   min_replay_size=64)
    traffic.update(prefill_sequences=8,
                   actors=min(traffic["actors"], 2),
                   envs_per_actor=min(traffic["envs_per_actor"], 2))
    traffic["max_batch_size"] = traffic["actors"] * traffic["envs_per_actor"]
    return harness.run_cell(
        cell, config, traffic, limits, BENCH, seed, 1.5, trace,
        process_start_ns=time.monotonic_ns(), require_tpu=False,
        log=lambda message: None)


def _state_unchanged(monkeypatch):
    from repro.policies import learning
    make = learning.make_learner

    def broken(*args, **kwargs):
        learner = make(*args, **kwargs)
        update = learner._update

        def same_state(state, sample):
            _, metrics, priorities = update(state, sample)
            return state, metrics, priorities

        learner._update = same_state
        return learner

    monkeypatch.setattr(learning, "make_learner", broken)


def _half_batch(monkeypatch):
    import jax
    from repro.policies import learning
    make = learning.make_learner

    def broken(*args, **kwargs):
        learner = make(*args, **kwargs)
        update = learner._update

        def on_half(state, sample):
            rows = sample.info.keys.shape[0] // 2
            return update(state, jax.tree.map(lambda x: x[:rows], sample))

        learner._update = on_half
        return learner

    monkeypatch.setattr(learning, "make_learner", broken)


def _altered_action(monkeypatch):
    from repro.policies.engine import PolicyEngine
    select = PolicyEngine.select

    def altered(self, *args, **kwargs):
        return (select(self, *args, **kwargs) + 1) % self.num_actions

    monkeypatch.setattr(PolicyEngine, "select", altered)


CELLS = [c["name"] for c in BENCH["workloads"]]


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_program_is_correct(cell_name):
    result = _run(cell_name)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert "setup_s" in result["metrics"]


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _altered_action])
def test_fault_is_not_correct(cell_name, fault, monkeypatch):
    fault(monkeypatch)
    result = _run(cell_name)
    assert not result["correct"], result["compared"]


PROGRAM_READERS = ("learner_input_ms", "learner_host_ms", "serve_batch_ms",
                   "serve_param_bytes_per_batch", "learner_step_ms",
                   "inference_rows_per_batch")


@pytest.mark.parametrize("cell_name", CELLS)
def test_traced_run_reads_the_program_telemetry(cell_name):
    result = _run(cell_name, seed=5, trace=True)
    assert result["correct"], result["compared"]
    metrics = result["metrics"]
    expected = {m["name"] for m in harness.cell_metrics(BENCH, cell_name,
                                                        "per_layer")}
    assert set(PROGRAM_READERS) & expected <= set(metrics), metrics
    assert all(metrics[n]["value"] > 0 for n in PROGRAM_READERS
               if n in expected)
    assert "setup_s" not in metrics

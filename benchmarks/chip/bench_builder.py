"""The program under test, as the benchmark assembles it.

``BenchPolicyBuilder`` is the program's ``TransformerPolicyBuilder`` with
the benchmark's probes at its layer boundaries: the inference handle each
actor receives is timed on the actor's side, the learner's replay stream,
steps and priority updates are recorded for the output check, and, in a
traced run, the calls into the replay, the learner, the engine and the
weight refresh are wrapped in ``jax.profiler.TraceAnnotation`` spans.  The
factory is picklable, so actor processes rebuild the same builder.
"""
from __future__ import annotations

import contextlib
import os
import struct
import threading
import time
import uuid

import jax
import numpy as np

from repro.envs import Catch
from repro.policies import TransformerPolicyBuilder, TransformerPolicyConfig

# one request on the actor side: start ns, end ns, rows, 1 if answered
RECORD = struct.Struct("<qqii")
RECORDED_STEPS = 3


def span(name: str, on: bool):
    return (jax.profiler.TraceAnnotation(f"bench/{name}") if on
            else contextlib.nullcontext())


class CatchFactory:
    """Picklable ``seed -> Catch`` at the configuration's board size."""

    def __init__(self, rows: int, columns: int):
        self.rows, self.columns = rows, columns

    def __call__(self, seed: int):
        return Catch(rows=self.rows, columns=self.columns, seed=seed)


class BuilderFactory:
    """Picklable ``spec -> BenchPolicyBuilder``."""

    def __init__(self, cfg_kwargs: dict, seed: int, log_dir: str,
                 traced: bool):
        self.cfg_kwargs = dict(cfg_kwargs)
        self.seed = seed
        self.log_dir = log_dir
        self.traced = traced

    def __call__(self, spec):
        return BenchPolicyBuilder(
            spec, TransformerPolicyConfig(**self.cfg_kwargs), self.seed,
            self.log_dir, self.traced)


class TimedInference:
    """The actor's inference handle, with every ``select_action`` round trip
    appended to this process's log: a ``RECORD`` header, then the rows'
    positions, the actions returned, and the windows sent."""

    def __init__(self, inference, log_dir: str):
        self._inference = inference
        os.makedirs(log_dir, exist_ok=True)
        path = os.path.join(log_dir, f"actor-{os.getpid()}-"
                                     f"{uuid.uuid4().hex[:8]}.bin")
        self._fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                           0o644)

    def select_action(self, windows, positions, client_id):
        windows = np.asarray(windows, np.float32)
        positions = np.asarray(positions, np.int32)
        rows = windows.shape[0]
        start = time.monotonic_ns()
        try:
            actions = self._inference.select_action(windows, positions,
                                                    client_id)
        except BaseException:
            os.write(self._fd, RECORD.pack(start, time.monotonic_ns(), rows,
                                           0))
            raise
        end = time.monotonic_ns()
        acts = np.asarray(actions, np.int32).reshape(rows)
        os.write(self._fd, RECORD.pack(start, end, rows, 1)
                 + positions.tobytes() + acts.tobytes() + windows.tobytes())
        return actions

    def __getattr__(self, name):
        return getattr(self._inference, name)


class _TracedSource:
    def __init__(self, source):
        self._source = source

    def get_variables(self, names=("policy",)):
        with span("learner.get_variables", True):
            return self._source.get_variables(names)


class LearnerRecord:
    """The learner's replay stream and steps, seen from outside.

    Keeps the first ``RECORDED_STEPS`` batches, each step's loss and the
    (keys, priorities) it sent to the replay, the optimizer's first moment
    after step 1 and the weights after the last recorded step (host copies),
    and the host clock at each step's end.  The first sample waits for
    ``release``, so that set-up can serve requests with the start weights."""

    def __init__(self, iterator, traced: bool):
        self._iterator = iterator
        self._traced = traced
        self.release = threading.Event()
        self.batches = []
        self.losses = []
        self.priorities = []
        self.first_moment = None
        self.params_after = None
        self.step_ends = []

    def __iter__(self):
        return self

    def priority_callback(self, callback):
        def recorded(keys, priorities):
            if len(self.priorities) < RECORDED_STEPS:
                self.priorities.append((np.array(keys), np.array(priorities)))
            return callback(keys, priorities)
        return recorded

    def __next__(self):
        self.release.wait()
        with span("replay.sample", self._traced):
            sample = next(self._iterator)
        if len(self.batches) < RECORDED_STEPS:
            self.batches.append(sample)
        return sample

    @property
    def ready(self) -> bool:
        return self.params_after is not None

    def attach(self, learner):
        step = learner.step

        def recorded_step():
            with span("learner.step", self._traced):
                metrics = step()
            n = len(self.step_ends) + 1
            if n <= RECORDED_STEPS:
                self.losses.append(float(metrics["loss"]))
                if n == 1:
                    self.first_moment = jax.device_get(
                        learner.state.opt_state.mu)
                if n == RECORDED_STEPS:
                    self.params_after = jax.device_get(learner.state.params)
            self.step_ends.append(time.monotonic_ns())
            return metrics

        learner.step = recorded_step


class BenchPolicyBuilder(TransformerPolicyBuilder):
    def __init__(self, spec, cfg, seed, log_dir, traced):
        super().__init__(spec, cfg, seed=seed)
        self.log_dir = log_dir
        self.traced = traced
        self.engine = None
        self.server = None
        self.record = None

    def make_policy(self, evaluation: bool = False):
        policy = super().make_policy(evaluation)
        make_engine = policy.make_engine

        def keep_engine(**kwargs):
            self.engine = make_engine(**kwargs)
            return self.engine

        policy.make_engine = keep_engine
        return policy

    def make_inference_server(self, variable_source, **kwargs):
        if self.traced:
            variable_source = _TracedSource(variable_source)
        self.server = super().make_inference_server(variable_source, **kwargs)
        if self.traced:
            select = self.engine.select

            def traced_select(*args, **kwargs):
                with span("engine.select", True):
                    return select(*args, **kwargs)

            self.engine.select = traced_select
        return self.server

    def make_inference_actor(self, inference, adder=None, adders=None):
        return super().make_inference_actor(
            TimedInference(inference, self.log_dir), adder=adder,
            adders=adders)

    def make_learner(self, iterator, priority_update_cb=None):
        self.record = LearnerRecord(iterator, self.traced)
        if priority_update_cb is not None:
            priority_update_cb = self.record.priority_callback(
                priority_update_cb)
        learner = super().make_learner(self.record, priority_update_cb)
        self.record.attach(learner)
        return learner

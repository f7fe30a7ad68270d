"""One benchmark run of one cell: set up, measure a window, check outputs.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell lives in a file of its own, found by the name ``BENCHMARK.json``
gives it: ``configs/<config>.json`` (through the configuration's ``file``),
``traffic/<mix>.json``, ``metrics/<metric>.py`` and ``limits/<cell>.json``.
A configuration names its model family, ``families/<family>.py``: the plain
reference of that model, its weights and what its work counts.
"""
from __future__ import annotations

import gc
import glob
import importlib.util
import json
import os
import shutil
import tempfile
import threading
import time

import numpy as np

CHIP_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(CHIP_DIR))
SETUP_TIMEOUT_S = 1100.0
DRAIN_TIMEOUT_S = 60.0


# ------------------------------------------------------------------ files
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def resolve_cell(bench: dict, name: str, root: str = ROOT):
    """(cell, config, traffic, limits) for the cell called ``name``."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    if "family" not in config:
        raise KeyError(f"configuration {cell['config']!r} names no family")
    traffic = load_json(os.path.join(CHIP_DIR, "traffic",
                                     f"{cell['traffic']}.json"))
    limits = load_json(os.path.join(CHIP_DIR, "limits", f"{name}.json"))
    return cell, config, traffic, limits


def cell_metrics(bench: dict, cell_name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def _load_module(kind: str, name: str):
    path = os.path.join(CHIP_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chip_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(metric_name: str):
    return _load_module("metrics", metric_name).read


def load_family(family_name: str):
    """The module ``families/<family_name>.py``: ``arch_from_config``,
    ``policy_fields``, ``make_params``, ``q_values``, ``last_q``,
    ``train_three``, ``sgd_step`` and ``learner_step_flops``."""
    return _load_module("families", family_name)


def derive_seeds(seed: int) -> dict:
    """Seeds for each consumer, all drawn from the one ``--seed``."""
    words = np.random.SeedSequence(int(seed)).generate_state(4)
    names = ("program", "weights", "prefill", "serve")
    return {n: int(w % (1 << 30)) for n, w in zip(names, words)}


def policy_kwargs(config: dict, traffic: dict) -> dict:
    """``TransformerPolicyConfig`` fields: the model's from the configuration
    through its family, learning and serving knobs from the traffic mix."""
    learner = traffic["learner"]
    return dict(
        load_family(config["family"]).policy_fields(config),
        epsilon=traffic["epsilon"],
        cache_slots=traffic["cache_slots"],
        backend="auto",
        learning_rate=learner["learning_rate"],
        discount=learner["discount"],
        sequence_length=learner["sequence_length"],
        period=learner["period"],
        batch_size=learner["batch_size"],
        target_update_period=learner["target_update_period"],
        min_replay_size=learner["min_replay_size"],
        max_replay_size=learner["max_replay_size"],
        samples_per_insert=learner["samples_per_insert"],
        priority_eta=learner["priority_eta"],
        importance_beta=learner["importance_beta"],
    )


def check_prefill_fits(traffic: dict):
    """Refuse a pre-fill the replay cannot hold, or that its rate limiter
    would block on before any sample is taken (the builder's limiter:
    minimum size ``min_replay_size // period + 1`` and an error buffer of
    ``max(2 * spi * batch, 100)`` samples)."""
    learner = traffic["learner"]
    if traffic["prefill_sequences"] > learner["max_replay_size"]:
        raise ValueError("prefill_sequences exceeds max_replay_size")
    spi = learner["samples_per_insert"]
    if spi <= 0:
        return
    min_size = learner["min_replay_size"] // learner["period"] + 1
    buffer = max(2 * spi * learner["batch_size"], 100, spi * min_size)
    most = min_size + int(buffer / spi) - 1
    if traffic["prefill_sequences"] > most:
        raise ValueError(f"prefill_sequences={traffic['prefill_sequences']} "
                         f"would block the replay's limiter (at most {most})")


# ------------------------------------------------------------ compile clock
class CompileClock:
    """Sums JAX's backend compile events (persistent-cache reads included)
    and counts persistent-cache hits, across all threads."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration
                self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


# ------------------------------------------------------------ actor logs
class ActorLogs:
    """The actors' request logs (``bench_builder.TimedInference``), read
    incrementally: each record is a ``RECORD`` header and, when answered,
    the rows' positions, actions and windows."""

    def __init__(self, log_dir: str, window: int, obs_dim: int):
        self.log_dir = log_dir
        self.window, self.obs_dim = window, obs_dim
        self._row_bytes = window * obs_dim * 4
        self._files = {}   # path -> [offset, last start, parsed records]

    def poll(self):
        from bench_builder import RECORD
        for path in glob.glob(os.path.join(self.log_dir, "actor-*.bin")):
            state = self._files.setdefault(path, [0, None, []])
            with open(path, "rb") as f:
                f.seek(state[0])
                buf = f.read()
            off = 0
            while off + RECORD.size <= len(buf):
                start, end, n, answered = RECORD.unpack_from(buf, off)
                size = RECORD.size + (n * (8 + self._row_bytes)
                                      if answered else 0)
                if off + size > len(buf):
                    break
                state[2].append((start, end, n, answered,
                                 buf[off + RECORD.size:off + size]))
                state[1] = start
                off += size
            state[0] += off

    def caught_up(self, count: int, after_ns: int) -> bool:
        """Each of ``count`` actor processes has logged a request begun
        after ``after_ns``: the actors are closed loop, so every earlier
        request of theirs has been answered."""
        self.poll()
        lasts = [s[1] for s in self._files.values() if s[1] is not None]
        return len(lasts) >= count and min(lasts) > after_ns

    def arrays(self) -> dict:
        """Every request as arrays: start and end (ns), rows, answered, and
        per answered row its request index, position, action and window."""
        self.poll()
        starts, ends, rows, ok = [], [], [], []
        req_of_row, positions, actions, windows = [], [], [], []
        for state in self._files.values():
            for start, end, n, answered, payload in state[2]:
                index = len(starts)
                starts.append(start)
                ends.append(end)
                rows.append(n)
                ok.append(bool(answered))
                if not answered:
                    continue
                positions.append(np.frombuffer(payload, np.int32, n, 0))
                actions.append(np.frombuffer(payload, np.int32, n, 4 * n))
                windows.append(np.frombuffer(
                    payload, np.float32, n * self.window * self.obs_dim,
                    8 * n).reshape(n, self.window, self.obs_dim))
                req_of_row.append(np.full(n, index))

        def cat(xs, dtype, shape=(0,)):
            return np.concatenate(xs) if xs else np.zeros(shape, dtype)

        return {
            "start": np.asarray(starts, np.int64),
            "end": np.asarray(ends, np.int64),
            "rows": np.asarray(rows, np.int64), "ok": np.asarray(ok, bool),
            "req_of_row": cat(req_of_row, np.int64),
            "position": cat(positions, np.int32),
            "action": cat(actions, np.int32),
            "window": cat(windows, np.float32,
                          (0, self.window, self.obs_dim)),
        }


# ------------------------------------------------------------ set-up parts
def _prefill(builder, table, traffic: dict, seed: int, env_factory):
    """Sequences of the adder's own structure from Catch episodes under
    uniform random actions, inserted before the program starts."""
    adder = builder.make_adder(table)
    env = env_factory(seed)
    rng = np.random.RandomState(seed)
    num_actions = builder.num_actions
    inserted = table.size()
    while table.size() - inserted < traffic["prefill_sequences"]:
        step = env.reset()
        adder.add_first(step)
        while not step.last():
            action = np.int32(rng.randint(num_actions))
            step = env.step(action)
            adder.add(action, step)


def _warm_engine(engine, params, buckets, obs_shape):
    """Run every prefill and decode bucket this cell's traffic can reach."""
    w = engine.window
    for b in buckets:
        keys = [("bench-warm", i) for i in range(b)]
        windows = np.zeros((b, w) + tuple(obs_shape), np.float32)
        engine.select(params, keys, windows, np.zeros(b, np.int64))
        engine.select(params, keys, windows, np.ones(b, np.int64))
        engine.release_client("bench-warm")


def _buckets(max_rows: int) -> list:
    out, b = [], 1
    while True:
        out.append(b)
        if b >= max_rows:
            return out
        b *= 2


HISTOGRAMS = ("inference/queue_wait_ms", "replay/insert_block_ms",
              "replay/sample_block_ms")


def _histogram_medians():
    from repro.telemetry import registry
    reg = registry.get_registry()
    out = {}
    for name in HISTOGRAMS:
        snap = reg.histogram(name).snapshot()
        out[name] = snap.get("p50") if snap.get("count") else None
    return out


def _drive_server(builder, server, traffic: dict, seed: int, env_factory,
                  log_dir: str, done):
    """The set-up's checked episodes, served with the start weights: one
    thread per actor of the traffic, each with its envs, plays
    ``checked_episodes`` Catch episodes per env closed loop through the
    inference server, as the program's own inference actor, its requests
    logged like the actors'.  Calls ``done(errors)`` when all have ended."""
    from bench_builder import TimedInference
    from repro.policies import TransformerPolicyBuilder
    envs_per_actor = traffic["envs_per_actor"]
    episodes = traffic["checked_episodes"] * envs_per_actor
    errors = []

    def play(index):
        try:
            actor = TransformerPolicyBuilder.make_inference_actor(
                builder, TimedInference(server, log_dir),
                adders=[None] * envs_per_actor)
            envs = [env_factory(seed + index * envs_per_actor + e)
                    for e in range(envs_per_actor)]
            steps = [env.reset() for env in envs]
            for e, step in enumerate(steps):
                actor.observe_first(step, env_id=e)
            ended = 0
            while ended < episodes:
                actions = actor.select_action(
                    np.stack([step.observation for step in steps]))
                for e, env in enumerate(envs):
                    steps[e] = env.step(actions[e])
                    if steps[e].last():
                        ended += 1
                        steps[e] = env.reset()
                        actor.observe_first(steps[e], env_id=e)
        except BaseException as error:   # reported by the caller
            errors.append(error)

    threads = [threading.Thread(target=play, args=(i,), daemon=True)
               for i in range(traffic["actors"])]
    for t in threads:
        t.start()

    def finish():
        for t in threads:
            t.join()
        done(errors)

    threading.Thread(target=finish, daemon=True).start()


# ------------------------------------------------------------------ run
def run_cell(cell: dict, config: dict, traffic: dict, limits: dict,
             bench: dict, seed: int, seconds: float, trace: bool, *,
             process_start_ns: int, require_tpu: bool = True,
             controls: bool = False, keep: dict = None, log=print) -> dict:
    """One run; returns the result object the command prints last.  With
    ``controls`` the result also carries the control's and the planted
    faults' readings; a ``keep`` dict receives what the learner recorded."""
    import jax
    devices = jax.devices()
    device = devices[0]
    if require_tpu and (device.platform != "tpu"
                        or len(devices) < cell["chips"]):
        raise SystemExit(
            f"needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devices)} {device.platform!r} device(s) "
            f"({device.device_kind}); nothing ran")
    import checks
    import flops
    import program_internals
    family = load_family(config["family"])
    peak = flops.peaks(device.device_kind) if require_tpu else None
    if require_tpu and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()

    from bench_builder import BuilderFactory, CatchFactory
    from repro.agents.builders import make_distributed_agent
    from repro.core import make_environment_spec

    seeds = derive_seeds(seed)
    check_prefill_fits(traffic)
    arch = family.arch_from_config(config)
    obs_shape = tuple(config["observation_shape"])
    env_factory = CatchFactory(*obs_shape)
    log_dir = tempfile.mkdtemp(prefix="chip-bench-")
    check_dir = os.path.join(log_dir, "check")
    logs = ActorLogs(log_dir, arch.window, arch.obs_dim)
    profile_dir = os.path.join(log_dir, "profile")
    factory = BuilderFactory(policy_kwargs(config, traffic), seeds["program"],
                             log_dir, trace)
    spec = make_environment_spec(env_factory(seeds["program"]))
    builder = factory(spec)
    if builder.num_actions != arch.num_actions:
        raise ValueError(f"environment has {builder.num_actions} actions, "
                         f"the configuration states {arch.num_actions}")
    actors = traffic["actors"]
    envs = traffic["envs_per_actor"]
    max_rows = traffic["max_batch_size"]

    drive_errors = []

    def release_learner(errors):
        drive_errors.extend(errors)
        builder.record.release.set()

    def before_launch(learner, table, counter):
        params = family.make_params(seeds["weights"], arch)
        learner.state = learner.state._replace(
            params=params, target_params=jax.tree.map(jax.numpy.copy,
                                                      params))
        del params
        _prefill(builder, table, traffic, seeds["prefill"], env_factory)
        served = learner.get_variables(("policy",))[0]
        _warm_engine(builder.engine, served, _buckets(max_rows), obs_shape)
        # served while the actors spawn; the learner waits for it
        _drive_server(builder, builder.server, traffic, seeds["serve"],
                      env_factory, check_dir, release_learner)

    try:
        dist = make_distributed_agent(
            builder, env_factory, num_actors=actors, seed=seeds["program"],
            launcher="multiprocess", builder_factory=factory, spec=spec,
            num_envs_per_actor=envs, inference="server",
            inference_max_batch_size=max_rows,
            inference_max_wait_ms=traffic["max_wait_ms"], telemetry=trace,
            restore=before_launch)
        record, server, learner = builder.record, builder.server, dist.learner
        deadline = time.monotonic() + SETUP_TIMEOUT_S
        while not (record.ready and logs.caught_up(actors, 0)):
            if drive_errors:
                raise drive_errors[0]
            if dist.launcher.should_stop():
                dist.launcher.join(timeout=30)
                raise RuntimeError("the program stopped during set-up")
            if time.monotonic() > deadline:
                raise TimeoutError(f"set-up not done in {SETUP_TIMEOUT_S} s")
            time.sleep(0.05)

        compiles_before = clock.compiles
        if trace:
            program_internals.reset_histograms(HISTOGRAMS)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(profile_dir, profiler_options=options)
            window_span = jax.profiler.TraceAnnotation("bench/window")
            window_span.__enter__()
        before = _snapshot(learner, server, builder, trace)
        t0 = time.monotonic_ns()
        time.sleep(seconds)
        t1 = time.monotonic_ns()
        after = _snapshot(learner, server, builder, trace)
        if trace:
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            medians = _histogram_medians()
        compiles_in_window = clock.compiles - compiles_before
        drain = time.monotonic() + DRAIN_TIMEOUT_S
        while (not logs.caught_up(actors, t1)
               and time.monotonic() < drain):
            time.sleep(0.05)
    except BaseException:
        builder.record.release.set()
        try:
            dist.stop()
        except Exception:   # already failing; the first error is the one
            pass
        shutil.rmtree(log_dir, ignore_errors=True)
        raise
    dist.stop()
    stats = device.memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")
    step_ends = np.asarray(record.step_ends, np.int64)
    recorded = {"batches": record.batches, "losses": record.losses,
                "priorities": record.priorities,
                "first_moment": record.first_moment,
                "params_after": record.params_after}
    del dist, builder, record, server, learner, factory
    # the telemetry registry's probes hold the server, and through it the
    # learner's state: drop them so the reference finds the device free
    from repro.telemetry import registry
    registry.unconfigure()
    gc.collect()
    log(f"device bytes still live after the program: "
        f"{sum(a.nbytes for a in jax.live_arrays())}")
    if keep is not None:
        keep.update(recorded=recorded, seeds=seeds, arch=arch)

    requests = logs.arrays()
    checked = ActorLogs(check_dir, arch.window, arch.obs_dim).arrays()
    if keep is not None:
        keep["checked"] = checked
    trace_summary = None
    if trace:
        import trace_reduce
        events = trace_reduce.load(profile_dir)
        trace_summary = trace_reduce.reduce(events)
        if keep is not None:
            keep["trace_events"] = events
    shutil.rmtree(log_dir, ignore_errors=True)

    window_s = (t1 - t0) / 1e9
    in_window = (requests["start"] >= t0) & (requests["start"] < t1)
    done_in_window = ((requests["end"] >= t0) & (requests["end"] < t1)
                      & requests["ok"])
    steps_in_window = int(((step_ends >= t0) & (step_ends < t1)).sum())
    attempted = int(in_window.sum())
    failed = int((in_window & ~requests["ok"]).sum())
    reported = [m["name"] for m in cell_metrics(bench, cell["name"],
                                                "end_to_end")]
    if "sgd_steps_per_s" in reported:
        attempted += steps_in_window

    # ---- metrics
    metrics = {}
    if not trace:
        e2e = {
            "setup_s": (t0 - process_start_ns) / 1e9,
            "sgd_steps_per_s": steps_in_window / window_s,
            "env_frames_per_s": float(requests["rows"][done_in_window].sum())
            / window_s,
        }
        for m in cell_metrics(bench, cell["name"], "end_to_end"):
            if m["name"] in e2e and m["name"] in reported:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        ctx = {
            "family": family, "arch": arch, "traffic": traffic,
            "window_s": window_s,
            "before": before, "after": after,
            "steps_in_window": steps_in_window, "medians": medians,
            "trace": trace_summary, "peak": peak,
        }
        for m in cell_metrics(bench, cell["name"], "per_layer"):
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    log(f"setup_s {(t0 - process_start_ns) / 1e9}  compiles "
        f"{clock.compiles} ({clock.cache_hits} cache hits, "
        f"{clock.seconds:.1f} s)  in window {compiles_in_window}  "
        f"steps in window {steps_in_window}  requests {attempted}  "
        f"metrics {json.dumps(metrics)}")

    # ---- outputs against the reference, after the program is gone
    compared, extra = checks.check_learner(
        family, arch, traffic["learner"], seeds["weights"], recorded,
        controls=controls)
    numbers, serve_extra = checks.check_serve(
        family, arch, seeds["weights"], checked, controls=controls)
    compared.update(numbers)
    extra.update(serve_extra)
    judged = {name: {"value": compared[name], "limit": limits["limits"][name]}
              for name in limits["limits"]}
    missing = [n for n in judged if judged[n]["value"] is None]
    correct = not missing and all(v["value"] <= v["limit"]
                                  for v in judged.values())

    result = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(devices), "memory_peak_bytes": memory_peak},
    }
    if trace:
        result["device"]["busy_s"] = trace_summary["busy_s"]
        result["device"]["window_s"] = trace_summary["window_s"]
        result["breakdown"] = {"device_ops": trace_summary["device_ops"],
                               "idle_gaps": trace_summary["idle_gaps"]}
    for name, value in list(compared.items()) + list(extra.items()):
        if name not in judged and not name.startswith(("control", "fault")):
            log(f"reading {name} {value}")
    if controls:
        result["controls"] = dict(
            extra, **{f"program.{k}": v for k, v in compared.items()})
    for name, v in judged.items():
        log(f"compared {name} {v['value']} limit {v['limit']}")
    result["compared"] = judged
    return result


def _snapshot(learner, server, builder, telemetry: bool) -> dict:
    """The counters the readers difference over the window; in a traced run
    also the program's telemetry registry (exact counts and sums, no
    reservoirs)."""
    stats = server.stats()
    out = {
        "learner_walltime": learner.learner_walltime,
        "learner_steps": len(builder.record.step_ends),
        "batches": stats["batches"],
        "prefill_rows": stats["prefill_rows"],
        "decode_rows": stats["decode_rows"],
    }
    if telemetry:
        from repro.telemetry import registry
        out["telemetry"] = registry.strip_reservoirs(registry.snapshot())
    return out

#!/usr/bin/env python3
"""The FedDF benchmark: one cell, one process, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json``: ``workloads``) names a configuration
(``bench/configs/<config>.json``, the client and server models' sizes,
of the model kind its ``"model"`` key names, ``bench/models/<kind>.py``),
a traffic mix (``bench/traffic/<traffic>.json``, the federated job) and
its chips.  The run makes every input from ``--seed`` (``inputs.py``),
builds the program's ``RoundEngine`` over them, with the client axis
sharded over the chips when there are several, and drives the program's
``sync`` driver for whole rounds:

* set-up: imports, inputs, the engine, and round 1, which compiles (or
  fetches from the compile cache) every program the window uses, and
  whose outputs the comparison reads;
* window: the rounds after round 1, up to the first round end at or past
  ``--seconds``; a round ends when its fused global's accuracies reach
  the host;
* check: once the window has closed and the program's state is freed,
  the plain reference (``reference.py``) replays round 1 and
  ``compare.py`` decides ``correct``.

``--trace 0`` reports the end-to-end metrics; ``--trace 1`` profiles the
window and reports the per-layer metrics, each computed by its reader
``bench/metrics/<name>.py``.  Without a TPU, or with fewer chips than the
cell asks for, the run exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (BENCH, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

import compare  # noqa: E402
import flops  # noqa: E402
import inputs as inputs_mod  # noqa: E402

#: JAX's persistent compilation cache and the profiler's output, at fixed
#: paths inside the checkout (the path is part of the cache's key)
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".bench_cache", "trace")
#: room for every program of a cell: JAX evicts the least recently used
#: programs past this size, and the machine's own cap (192 MiB) evicted
#: programs of the ladder cell
CACHE_BYTES = 2 ** 30
#: the program's seeds must stay below 2**31 after per-round offsets
SEED_SPAN = 2 ** 31 - 2 ** 20
#: the model kinds, one module each, and the kind of a configuration
#: without a "model" key
MODELS = os.path.join(BENCH, "models")
DEFAULT_KIND = "tiny_transformer"


class NoChip(Exception):
    pass


# -- the manifest and the files it names --------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT):
    """(workload, config, traffic, manifest) of the cell ``name``."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    config = load_json(os.path.join(root, "bench", "configs",
                                    f"{cell['config']}.json"))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     f"{cell['traffic']}.json"))
    return cell, config, traffic, manifest


def load_kind(name: str, folder: str = MODELS):
    """The model kind ``<folder>/<name>.py``.  A kind gives

    * ``model_dict(prototype, config)``: the model dict of one of the
      configuration's prototypes, with every size the kind needs and
      ``vocab_size``, ``seq_len`` and ``n_classes``, which the inputs read;
    * ``net(bundle, model)``: the program's net, from
      ``api/registries.py:get_model`` by the kind's registry name;
    * ``init_params(key, model, dtype)`` and ``forward(params, x, model)``:
      the reference's weights, whose leaves carry the program's leaf
      paths, and its (logits, auxiliary training loss), the loss 0.0 in a
      kind without one;
    * ``forward_flops_per_token(model)``: model FLOPs of one token's
      forward pass, over the active parameters."""
    path = os.path.join(folder, f"{name}.py")
    if not os.path.isfile(path):
        found = sorted(f[:-3] for f in os.listdir(folder)
                       if f.endswith(".py") and not f.startswith("_"))
        raise ValueError(f"unknown model kind {name!r}; kinds in "
                         f"{folder}: {found}")
    return _load_module(path, f"model_kind_{name}")


def config_kind(config: dict, folder: str = MODELS):
    """The model kind the configuration's ``"model"`` key names."""
    return load_kind(config.get("model", DEFAULT_KIND), folder)


def model_dicts(config: dict, kind) -> list:
    """One model dict per prototype, in the reference's terms."""
    return [kind.model_dict(p, config) for p in config["prototypes"]]


# -- the chip, the compile cache and compile events ---------------------------

def check_devices(chips: int):
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:  # no backend at all
        raise NoChip(str(e))
    if jax.default_backend() != "tpu" or len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {jax.default_backend()} device(s)")
    return devices[:chips]


def use_compile_cache(path: str) -> None:
    """Every program of the cell goes into JAX's persistent cache at
    ``path``, however short its compile, whatever the environment says
    (a machine's own size cap evicted programs of the ladder cell)."""
    import jax
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", CACHE_BYTES)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileWatch:
    """Programs compiled or loaded from the persistent cache (JAX records
    its backend-compile event around both), and of those the cache hits,
    with their times, from JAX's own monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.compiles = []  # (perf_counter at the event, seconds)
        self.hits = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == self.COMPILE:
            self.compiles.append((time.perf_counter(), float(duration)))

    def _event(self, event, **kw):
        if event == self.HIT:
            self.hits.append(time.perf_counter())

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def between(self, t0: float, t1: float):
        """(programs compiled or loaded, of them cache hits, their seconds)
        inside [t0, t1]."""
        c = [d for t, d in self.compiles if t0 <= t <= t1]
        h = [t for t in self.hits if t0 <= t <= t1]
        return len(c), len(h), sum(c)


# -- the program --------------------------------------------------------------

def build_engine(config: dict, traffic: dict, inp, seed: int, kind,
                 chips: int = 1):
    """The program's ``RoundEngine`` over the benchmark's inputs: nets from
    the model registry at the configuration's widths, the job's
    ``FLConfig``, and the unlabeled pool as the distillation source.  On
    several chips the client axis shards over a mesh of them
    (``launch/mesh.py:make_client_mesh``)."""
    from repro.api.registries import TaskBundle
    from repro.core.engine import FLConfig, RoundEngine
    from repro.core.feddf import FusionConfig
    from repro.data.distill_sources import UnlabeledDataset
    from repro.data.synthetic import Dataset

    models = model_dicts(config, kind)
    m0 = models[0]
    bundle = TaskBundle(dataset=None, distill_shape=(m0["seq_len"],),
                        vocab=m0["vocab_size"],
                        model_kwargs={"vocab": m0["vocab_size"],
                                      "n_classes": m0["n_classes"],
                                      "seq_len": m0["seq_len"]})
    nets = [kind.net(bundle, m) for m in models]
    feddf = traffic["strategy"] == "feddf"
    fusion = FusionConfig()
    if feddf:
        fusion = FusionConfig(
            max_steps=int(traffic["distill_steps"]),
            patience=int(traffic["patience"]),
            eval_every=int(traffic["eval_every"]),
            batch_size=int(traffic["distill_batch"]),
            lr=float(traffic["distill_lr"]),
            temperature=float(traffic["temperature"]),
            use_fused_kernel=traffic["use_fused_kernel"],
            logit_bank=traffic["logit_bank"],
            bank_dtype=traffic["bank_dtype"])
    cfg = FLConfig(rounds=10 ** 9,
                   client_fraction=float(traffic["client_fraction"]),
                   local_epochs=int(traffic["local_epochs"]),
                   local_batch_size=int(traffic["local_batch_size"]),
                   strategy=traffic["strategy"], seed=seed,
                   local_optimizer="adam",
                   local_adam_lr=float(traffic["local_lr"]), fusion=fusion)
    n_cls = m0["n_classes"]
    ds = lambda s: Dataset(s.x, s.y, n_cls)
    proto = [k % len(nets) for k in range(len(inp.parts))]
    engine = RoundEngine(nets, proto, ds(inp.train), inp.parts, ds(inp.val),
                         ds(inp.test), cfg,
                         source=UnlabeledDataset(inp.pool) if feddf else None,
                         heterogeneous=len(nets) > 1)
    if chips > 1:
        from repro.launch.mesh import make_client_mesh
        engine.attach_mesh(make_client_mesh(chips))
    return engine, proto


def initial_globals(engine) -> list:
    """The engine's initial globals, replicated over its mesh when it has
    one: the fused globals of every later round come back so placed, and
    round 1 must compile the programs they take.  The program's own
    drivers start from ``engine.init_globals()`` unplaced, so on a mesh
    they compile the client update again in round 2 (PERF.md)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    globals0 = engine.init_globals()
    if engine.mesh is not None:
        globals0 = jax.device_put(
            globals0, NamedSharding(engine.mesh, PartitionSpec()))
    return jax.block_until_ready(globals0)


class RoundOneTap:
    """Reads what round 1 produced through the engine's own phase calls:
    each client's change from the initial global (per leaf), how each
    group's client stack lies over the devices, the logit bank rows, each
    group's fused change, and for each distillation its first chunk (the
    compiled program's own call): per leaf, the change of the student
    over the chunk's steps and the gradient norm that Adam's second
    moment holds after them.  Removed after round 1."""

    def __init__(self, engine):
        import jax
        import jax.numpy as jnp
        from repro.core import feddf as feddf_mod
        from reference import ADAM_B2

        self.engine, self.feddf = engine, feddf_mod
        self.clients = self.fused = self.bank = None
        self.chunks, self.layout = [], []
        self._g0 = None

        def norms(tree, base, stacked):
            def one(a, b):
                d = a.astype(jnp.float32) - (b[None] if stacked else b
                                             ).astype(jnp.float32)
                axes = tuple(range(1 if stacked else 0, d.ndim))
                return jnp.sqrt(jnp.sum(d * d, axis=axes))
            return {jax.tree_util.keystr(p): one(a, b) for (p, a), b in zip(
                jax.tree_util.tree_leaves_with_path(tree),
                jax.tree.leaves(base))}

        stack_norms = jax.jit(lambda s, b: norms(s, b, True))
        tree_norms = jax.jit(lambda s, b: norms(s, b, False))
        train, aggregate = engine.train_clients, engine.aggregate
        resolve, get_chunk = feddf_mod.resolve_bank, feddf_mod._get_chunk

        @jax.jit
        def chunk_norms(p1, p0, nu, steps):
            # Adam's bias-corrected second moment: the mean of g^2 over
            # the chunk's steps, weighted as the optimizer weighs them
            corr = 1.0 - ADAM_B2 ** steps.astype(jnp.float32)
            grad = {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(v) / corr)
                    for p, v in jax.tree_util.tree_leaves_with_path(nu)}
            return norms(p1, p0, False), grad

        def first_chunk(fn):
            def call(params, opt_state, key, step0, *extra):
                if int(step0) != 0:
                    return fn(params, opt_state, key, step0, *extra)
                # the chunk may donate its inputs: keep the start apart
                p0 = jax.tree.map(jnp.copy, params)
                out = fn(params, opt_state, key, step0, *extra)
                change, grad = chunk_norms(out[0], p0, out[1].nu, out[3])
                self.chunks.append(
                    {"change": {k: float(v) for k, v in change.items()},
                     "grad": {k: float(v) for k, v in grad.items()}})
                return out
            return call

        def get_chunk_(*a, **kw):
            fn, extra = get_chunk(*a, **kw)
            return first_chunk(fn), extra

        def train_clients(t, globals_, batches):
            groups = train(t, globals_, batches)
            self._g0 = list(globals_)
            self.clients = []
            for g, base in zip(groups, globals_):
                if g.stack is None:
                    self.clients.append([])
                    continue
                leaf = jax.tree.leaves(g.stack)[0]
                self.layout.append(
                    (leaf.shape[0],
                     sorted(d.id for d in leaf.sharding.device_set),
                     leaf.sharding.shard_shape(leaf.shape)[0]))
                n = {k: np.asarray(v) for k, v in
                     stack_norms(g.stack, base).items()}
                k_real = len(next(iter(n.values())))
                self.clients.append([{k: float(v[i]) for k, v in n.items()}
                                     for i in range(k_real)])
            return groups

        def aggregate_(t, groups, state):
            out = aggregate(t, groups, state)
            self.fused = [{k: float(v) for k, v in
                           tree_norms(g, b).items()}
                          for g, b in zip(out[0], self._g0)]
            return out

        def resolve_bank(*a, **kw):
            bank, reason = resolve(*a, **kw)
            if bank is not None and self.bank is None:
                rows = np.asarray(bank.logits, np.float32)
                if bank.scales is not None:
                    rows = rows * np.asarray(bank.scales)[:, None]
                self.bank = rows
            return bank, reason

        engine.train_clients = train_clients
        engine.aggregate = aggregate_
        feddf_mod.resolve_bank = resolve_bank
        feddf_mod._get_chunk = get_chunk_
        self._resolve, self._get_chunk = resolve, get_chunk

    def close(self):
        for name in ("train_clients", "aggregate"):
            self.engine.__dict__.pop(name, None)
        self.feddf.resolve_bank = self._resolve
        self.feddf._get_chunk = self._get_chunk


class Window:
    """The round driver's ``log_fn``: marks round ends, takes round 1's
    outputs, starts the window after round 1 and asks for a stop at the
    first round end at or past ``seconds`` into it (right after round 1
    when ``seconds`` is None)."""

    def __init__(self, seconds: float, n_groups: int, on_round_one,
                 on_start, on_stop):
        self.seconds, self.n_groups = seconds, n_groups
        self.on_round_one, self.on_start, self.on_stop = (
            on_round_one, on_start, on_stop)
        self.logs = {}
        self.t_window = None
        self.ends = []

    def __call__(self, ev):
        p, log = ev if isinstance(ev, tuple) else (0, ev)
        self.logs.setdefault(log.round, []).append(log)
        if p != self.n_groups - 1:
            return None
        now = time.perf_counter()
        if self.t_window is None:
            self.on_round_one(self.logs[log.round])
            if self.seconds is None:  # round 1 alone
                return True
            self.on_start()
            self.t_window = time.perf_counter()
            return None
        self.ends.append(now)
        if now - self.t_window >= self.seconds:
            self.on_stop()
            return True
        return None


# -- the run ------------------------------------------------------------------

def program_round_one(tap: RoundOneTap, logs) -> "object":
    import reference
    return reference.RoundOne(
        clients=tap.clients, first_grad={}, bank=tap.bank, fused=tap.fused,
        chunks=tap.chunks,
        distilled=[l.distill_steps > 0 for l in logs],
        test_acc=[l.test_acc for l in logs], val_acc=[l.val_acc for l in logs],
        pre_acc=[l.pre_distill_acc for l in logs],
        ens_acc=logs[0].ensemble_acc)


def load_reader(name: str):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    folder = os.path.join(BENCH, "metrics")
    if folder not in sys.path:
        sys.path.insert(0, folder)
    return _load_module(os.path.join(folder, f"{name}.py"),
                        f"metric_{name}").read


def _load_module(path: str, module_name: str):
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_cell(cell: dict, config: dict, traffic: dict, manifest: dict, *,
             seed: int, seconds: float, trace: bool, require_chip: bool = True,
             cache_dir=CACHE_DIR, log=print, models_dir: str = MODELS) -> dict:
    """One run of one cell; returns the result object.  Raises
    :class:`NoChip` before doing any work when the chips are missing
    (unless ``require_chip`` is off, as the harness's own tests run), and
    ValueError when the configuration names no model kind of
    ``models_dir``."""
    kind = config_kind(config, models_dir)
    t_imports0 = time.perf_counter()
    import jax
    chips = int(cell["chips"])
    devices = check_devices(chips) if require_chip else jax.devices()[:chips]
    if cache_dir is not None:
        use_compile_cache(cache_dir)
    watch = CompileWatch()
    import reference as ref_mod
    from repro.core import logit_bank
    from repro.drivers.sync import SyncDriver
    from repro.obs import trace as obs
    t_inputs0 = time.perf_counter()

    models = model_dicts(config, kind)
    fl_seed = int(seed) % SEED_SPAN
    inp = inputs_mod.make_inputs(seed, models[0], traffic, len(models))
    t_engine0 = time.perf_counter()
    engine, proto = build_engine(config, traffic, inp, fl_seed, kind, chips)
    globals0 = initial_globals(engine)
    t_round0 = time.perf_counter()

    tap = RoundOneTap(engine)
    state = {"logs1": None, "rec": None, "t_trace0": None, "t_trace1": None}
    trace_dir = os.path.join(TRACE_DIR, cell["name"])

    def round_one(logs):
        state["logs1"] = logs
        tap.close()

    def start():
        if trace:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
            # the recorder starts the profiler itself; it gets options
            # without the Python tracer, which would slow every host call
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            begin = jax.profiler.start_trace
            jax.profiler.start_trace = \
                lambda d, **kw: begin(d, profiler_options=opts)
            try:
                state["rec"] = obs.arm(profile_dir=trace_dir)
            finally:
                jax.profiler.start_trace = begin
            state["t_trace0"] = time.perf_counter()

    def stop():
        if trace:
            state["t_trace1"] = time.perf_counter()
            obs.disarm()

    win = Window(seconds, len(models), round_one, start, stop)
    try:
        results, _, _ = SyncDriver().run(engine, log_fn=win,
                                         init_globals=globals0)
        jax.block_until_ready([r.global_params for r in results])
    finally:
        tap.close()
        watch.close()
        obs.disarm()

    rounds = len(win.ends)
    window_s = win.ends[-1] - win.t_window
    setup_s = win.t_window - T_START
    n_comp, n_hit, comp_s = watch.between(win.t_window, win.ends[-1])
    peak = peak_bytes(devices)
    round_logs = [win.logs[t] for t in sorted(win.logs)]
    failed = sum(1 for logs in round_logs[1:]
                 if any(not np.isfinite(l.test_acc) or not l.fused
                        or l.rolled_back for l in logs))
    s1_comp, s1_hit, s1_comp_s = watch.between(t_round0, win.t_window)
    log(f"setup split: imports_s={t_inputs0 - T_START:.3f} "
        f"(of which jax {t_inputs0 - t_imports0:.3f}) "
        f"inputs_s={t_engine0 - t_inputs0:.3f} "
        f"engine_and_weights_s={t_round0 - t_engine0:.3f} "
        f"warmup_round_s={win.t_window - t_round0:.3f} "
        f"(programs compiled or loaded={s1_comp}, of them cache hits="
        f"{s1_hit}, in {s1_comp_s:.3f} s) setup_s={setup_s:.3f}")
    log(f"window: rounds={rounds} window_s={window_s:.3f} "
        f"round_ends_s={[round(t - win.t_window, 3) for t in win.ends]} "
        f"programs compiled or loaded={n_comp}, of them cache hits={n_hit}, "
        f"in {comp_s:.3f} s; peak_bytes_in_use={peak}")
    for g, (rows, devs, per) in enumerate(tap.layout):
        log(f"client stacks: group {g}: {rows} clients over devices {devs}, "
            f"{per} per device")

    metrics, extra = {}, {}
    if trace:
        import tracefile
        tr = tracefile.load_dir(trace_dir)
        ctx = {"trace": tr, "rounds": rounds,
               "window_s": state["t_trace1"] - state["t_trace0"],
               "round_s": window_s / rounds, "chips": chips,
               "spans": list(state["rec"].spans),
               "compiles": n_comp,
               "flops": window_flops(kind, models, traffic, inp, proto,
                                     fl_seed, rounds),
               "peaks": device_peaks(devices[0].device_kind),
               "traffic": traffic, "models": models}
        for m in manifest["per_layer"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        extra["busy_s"] = tracefile.busy_s(tr)
        extra["window_s"] = ctx["window_s"]
        extra["breakdown"] = tracefile.breakdown(tr)
        log(f"trace: busy_s={extra['busy_s']:.6f} "
            f"window_s={extra['window_s']:.6f}")
    else:
        e2e = {"round_s": window_s / rounds, "peak_hbm_gib": peak / 2 ** 30,
               "setup_s": setup_s}
        for m in manifest["end_to_end"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}

    # the check: free the program's state, then replay round 1 plainly
    prog = program_round_one(tap, state["logs1"])
    del results, engine, globals0, tap, win
    logit_bank.PERSISTENT_BANK.clear()
    gc.collect()
    t_ref0 = time.perf_counter()
    limits = compare.load_limits()
    ref_models = [ref_mod.Model(m, traffic, kind) for m in models]
    ref = ref_mod.round_one(ref_models, traffic, inp, proto, fl_seed,
                            len(models) > 1)
    nums = compare.numbers(prog, ref)
    correct = compare.judge(nums, limits)
    log(f"reference: live_bytes_max={ref.live_bytes_max} "
        f"peak_bytes_in_use={peak_bytes(devices)} (the process's, after "
        f"the replay; "
        f"the window's {peak})")
    log(f"reference: {time.perf_counter() - t_ref0:.3f} s "
        f"({ref.seconds}); val accuracies at its checkpoints "
        f"{ref.val_history}; round 1 test/val/pre-distillation/ensemble "
        f"accuracy: program's {prog.test_acc}/{prog.val_acc}/"
        f"{prog.pre_acc}/{prog.ens_acc}, reference's {ref.test_acc}/"
        f"{ref.val_acc}/{ref.pre_acc}/{ref.ens_acc}")
    for k, rows in compare.worst_leaves(prog, ref).items():
        log(f"largest {k} gaps (gap, leaf, program, reference): {rows}")
    for k, v in compare.not_compared(prog, ref).items():
        log(f"not compared: {k} {v!r}")

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        device["busy_s"] = extra["busy_s"]
        device["window_s"] = extra["window_s"]
    out = {"correct": bool(correct), "attempted": rounds, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = extra["breakdown"]
    out["checks"] = compare.as_json(nums, limits)
    out["_check_lines"] = compare.lines(nums, limits)
    return out


def peak_bytes(devices) -> int:
    """The process's peak device bytes on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def window_flops(kind, models, traffic, inp, proto, fl_seed, rounds) -> dict:
    """Model FLOPs of the window's rounds (2 .. rounds + 1), by phase."""
    act = flops.cohorts(fl_seed, len(inp.parts),
                        float(traffic["client_fraction"]), rounds + 1)[1:]
    tot = {}
    for a in act:
        for k, v in flops.round_flops(kind, lambda p: models[p], traffic,
                                      inp, proto, a).items():
            tot[k] = tot.get(k, 0.0) + v
    return tot


def device_peaks(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(table)}")
    return table[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, config, traffic, manifest = load_cell(args.workload)
    err = lambda s: print(s, file=sys.stderr, flush=True)
    try:
        out = run_cell(cell, config, traffic, manifest, seed=args.seed,
                       seconds=args.seconds, trace=bool(args.trace), log=err)
    except NoChip as e:
        err(f"run.py: {e}")
        return 2
    for line in out.pop("_check_lines"):
        err(line)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

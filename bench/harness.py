"""One run of one benchmark cell.

A cell names a model configuration (``configs/<config>.json`` with its
plain reference beside it) and a traffic mix (``traffic/<traffic>.json``,
overridden by ``workloads/<cell>.json``, which also holds the cell's
correctness limits). Per-layer metrics are small readers in
``metrics/<name>.py``. Everything is found by the names in
``BENCHMARK.json``, so a new cell, configuration or metric is a new file
and a new entry, and no file here changes.

A run:

1. builds the job as ``launch/train.py`` builds it
   (``build_train_setup``, ``InputConfig``, then ``Trainer``), with
   weights the benchmark draws on the device from ``--seed`` and a pool
   of images drawn from it;
2. drives that job through its first three steps (the warm-up, which
   compiles) and reads what the correctness check compares, then through
   more steps of the same trainer run to time a steady step, which sets
   how many steps the window takes;
3. runs ``Trainer.run`` for the measured window, each step timed by a
   clock around the train step the trainer calls, the window closed by
   ``block_until_ready`` on the final state;
4. frees the program's state, runs the plain reference over the same
   three steps and compares.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import statistics
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CHECK_STEPS = 3
# Steps after the checked ones, before the window, so the last of them
# time a steady step: where the host feed is slower than the device, the
# first steps drain the prefetched batches (about 11 steps of
# resnet50.b32) and run fast.
CALIBRATION_STEPS = 20
# Seconds at the end of the window that a --trace 1 run traces.
TRACE_SECONDS = 2.0
# Everything ``compare`` reads; a cell compares those its file gives a
# limit for.
NUMBERS = ("loss0", "loss1", "loss2", "grad", "grad_med", "update",
           "update_med", "bn", "bn_med", "bn_vec", "bn_vec_med")


# ------------------------------------------------------------------ spec

def _load_json(*parts) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    bench_dir: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict[str, Optional[float]]
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def model(self) -> Dict:
        return self.config["model"]

    @property
    def recipe(self) -> Dict:
        return self.config["recipe"]

    @property
    def global_batch(self) -> int:
        return int(self.traffic["per_chip_batch"]) * self.chips

    def reference(self):
        path = os.path.join(self.bench_dir, "configs",
                            self.config["reference"] + ".py")
        if path not in _REFERENCES:
            _REFERENCES[path] = load_module(
                path, "bench_ref_" + self.config["reference"])
        return _REFERENCES[path]


_REFERENCES: Dict[str, Any] = {}


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = _load_json(root, "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    bench = os.path.join(root, "bench")
    config = _load_json(bench, "configs", entry["config"] + ".json")
    traffic = _load_json(bench, "traffic", entry["traffic"] + ".json")
    cell_file = os.path.join(bench, "workloads", name + ".json")
    own = _load_json(cell_file) if os.path.exists(cell_file) else {}
    traffic = {**traffic, **own.get("traffic", {})}

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name=name, bench_dir=bench, chips=int(entry["chips"]),
                config=config,
                traffic=traffic, limits=dict(own.get("limits", {})),
                end_to_end=[m for m in spec["end_to_end"] if applies(m)],
                per_layer=[m for m in spec["per_layer"] if applies(m)])


def seed_key(seed: int):
    """A JAX key from any whole number (more than 32 bits included)."""
    import jax.numpy as jnp
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


# ----------------------------------------------------------- the program

@dataclasses.dataclass
class Job:
    """The program's job, built once: the compiled step and its state."""
    cell: Cell
    state: Any
    train_step: Callable
    put_batch: Callable
    state_shardings: Any
    input_cfg: Any
    prog_seed: int
    paths: List[str]
    make_params: Callable  # key -> the program's params tree
    initial: Any = None  # host copy of the starting state, and shardings
    readers: Any = None  # program_readers(self), made on first use


def program_config(cell: Cell):
    from repro.configs import get_config
    m = cell.model
    return dataclasses.replace(
        get_config(cell.config["program_arch"]), name=cell.config["name"],
        conv_stages=tuple(m["conv_stages"]), conv_width=m["conv_width"],
        num_classes=m["num_classes"], image_size=m["image_size"],
        n_layers=2 + 3 * sum(m["conv_stages"]))


def build_job(cell: Cell, devices, prog_seed: int = 0,
              keep_initial: bool = False) -> Job:
    """The job as ``launch/train.py:main`` builds it. ``prog_seed`` is
    the program's own seed (its augmentation stream); the fused input
    compiles it into the step, so it stays fixed and ``--seed`` draws
    the weights and images instead."""
    import jax
    import jax.numpy as jnp
    from repro.configs import InputConfig, OptimizerConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_train_setup

    r, t = cell.recipe, cell.traffic
    mesh = make_mesh((cell.chips, 1), ("data", "model"),
                     devices=devices[:cell.chips])
    aug = t["augment"]
    input_cfg = InputConfig(fused=t["input"] == "fused", augment=True,
                            max_shift=aug["max_shift"],
                            mean=tuple(aug["mean"]), std=tuple(aug["std"]))
    opt_cfg = OptimizerConfig(
        kind=r["optimizer"], schedule=r["schedule"], mu1=r["mu1"],
        mu2=r["mu2"], eps=r["eps"], eta_rmsprop=r["eta_rmsprop"],
        beta_center=r["beta_center"], beta_period=r["beta_period"],
        weight_decay=r["weight_decay"],
        base_lr_per_256=r["base_lr_per_256"])
    gb = cell.global_batch
    _, state, train_step, _, put_batch, shardings = build_train_setup(
        program_config(cell), global_batch=gb, seq_len=128,
        opt_cfg=opt_cfg, steps_per_epoch=-(-r["train_images"] // gb),
        mesh=mesh, dp_mode=r["dp_mode"],
        compute_dtype=jnp.dtype(r["compute_dtype"]), seed=prog_seed,
        sync_bn=r["sync_bn"], compression=r["compression"],
        bucket_bytes=r["bucket_mib"] << 20, input_cfg=input_cfg)

    ref = cell.reference()
    spec = ref.param_spec(cell.model)
    flat, treedef = jax.tree_util.tree_flatten_with_path(state["params"])
    paths = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    got = {p: tuple(x.shape) for p, (_, x) in zip(paths, flat)}
    want = {p: tuple(s) for p, s in spec}
    if got != want:
        raise ValueError(
            "the program's parameters differ from the configuration: "
            f"{sorted(set(got.items()) ^ set(want.items()))[:6]}")
    index = {p: i for i, (p, _) in enumerate(spec)}
    out_sh = jax.tree_util.tree_unflatten(
        treedef, [x.sharding for _, x in flat])

    def draw(key):
        return jax.tree_util.tree_unflatten(treedef, [
            ref.init_leaf(key, index[p], p, got[p]) for p in paths])

    make_params = jax.jit(draw, out_shardings=out_sh)
    initial = None
    if keep_initial:
        rest = {k: v for k, v in state.items() if k != "params"}
        initial = (jax.device_get(rest),
                   jax.tree.map(lambda x: x.sharding, rest))
    return Job(cell=cell, state=state, train_step=train_step,
               put_batch=put_batch, state_shardings=shardings,
               input_cfg=input_cfg, prog_seed=prog_seed, paths=paths,
               make_params=make_params, initial=initial)


def fresh_state(job: Job, key):
    """A state to start from: the benchmark's weights for ``key``, and
    the optimizer and BN state the program built. The first call hands
    over the program's own arrays; later calls (several seeds in one
    process) place host copies of them again, which ``keep_initial``
    made."""
    import jax
    if job.state is not None:
        st, job.state = job.state, None
        free(st["params"])
    else:
        if job.initial is None:
            raise RuntimeError("build_job(keep_initial=True) is needed to "
                               "start more than once")
        host, shardings = job.initial
        st = jax.device_put(host, shardings)
    st = dict(st)
    st["params"] = job.make_params(key)
    return st


class SpannedSource:
    """The program's input source, each ``batch_at`` inside a host span."""

    def __init__(self, source):
        self.source = source
        self.sample_offset = getattr(source, "sample_offset", 0)

    @property
    def batch(self):
        return self.source.batch

    def batch_at(self, step):
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("bench.batch_at"):
            return self.source.batch_at(step)


class TimedStep:
    """The train step the trainer calls, with the benchmark's clock: the
    time each call begins (a step lasts until the next begins; the last
    until the window closes), and an optional hook on each result.

    With ``trace_dir`` set, the profiler starts as call ``trace_from``
    begins and a ``bench.window`` span opens with it; ``measure`` closes
    both when the window closes. Only the window's last steps are
    traced: a trace of every step is too large to read in a run's time."""

    def __init__(self, step):
        self.step = step
        self.starts: List[float] = []
        self.hook: Optional[Callable] = None
        self.calls = 0
        self.trace_dir: Optional[str] = None
        self.trace_from = 0
        self.span = None

    def __call__(self, state, batch):
        import jax
        from jax.profiler import TraceAnnotation
        if self.trace_dir and self.span is None \
                and self.calls == self.trace_from:
            jax.profiler.start_trace(self.trace_dir)
            self.span = TraceAnnotation("bench.window")
            self.span.__enter__()
        self.starts.append(time.perf_counter())
        with TraceAnnotation("bench.train_step"):
            out = self.step(state, batch)
        if self.hook is not None:
            self.hook(self.calls, out)
        self.calls += 1
        return out

    def end_trace(self):
        import jax
        if self.span is not None:
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.span, self.trace_dir = None, None

    def reset(self):
        self.starts, self.calls = [], 0


def make_source(job: Job, pool):
    from repro.launch.train import _wrap_train_source
    return SpannedSource(_wrap_train_source(
        pool, job.input_cfg, seed=job.prog_seed,
        global_batch=job.cell.global_batch, is_conv=True))


def run_trainer(job: Job, timed: TimedStep, state, source, steps: int):
    from repro.training import Trainer, TrainerConfig
    tcfg = TrainerConfig(epochs=1, steps_per_epoch=steps,
                         eval_every_epochs=0, val_batches=0,
                         checkpoint_every=0, log_every=1,
                         data_workers=job.input_cfg.num_workers)
    return Trainer(timed, state, source, tcfg, put_batch=job.put_batch,
                   state_shardings=job.state_shardings).run()


# ------------------------------------------------------------ readings

def leaf_gaps(prog, ref, keep=None) -> np.ndarray:
    """Per leaf, |norm(prog) - norm(ref)| over max(norm(ref), the median
    leaf's norm of ref)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is not None:
        prog, ref = prog[keep], ref[keep]
    if not np.all(np.isfinite(prog)):
        return np.full(prog.shape, math.inf)
    den = np.maximum(ref, np.median(ref))
    return np.abs(prog - ref) / den


def moving(grad_norms) -> np.ndarray:
    """Leaves whose reference gradient is above a thousandth of the
    median leaf's: the others move by round-off alone."""
    g = np.asarray(grad_norms, np.float64)
    return g > 1e-3 * np.median(g)


def vector_gaps(prog: List, ref: List) -> np.ndarray:
    """Per vector, norm(prog - ref) over norm(ref): unlike a gap of
    norms, this sees a change of direction."""
    out = []
    for p, r in zip(prog, ref):
        p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
        if p.shape != r.shape:
            raise ValueError(f"statistics of shape {p.shape} against "
                             f"{r.shape} in the reference")
        ok = np.all(np.isfinite(p))
        out.append(np.linalg.norm(p - r) / np.linalg.norm(r) if ok
                   else math.inf)
    return np.asarray(out)


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog`` and ``ref`` hold the three losses, each leaf's step-1
    gradient norm (``grad``) and change over three steps (``update``),
    and the BN statistics after step 1 (``bn``: each site's mean, then
    each site's var)."""
    keep = moving(ref["grad"])
    out = {}
    for k in range(CHECK_STEPS):
        lp, lr = float(prog["loss"][k]), float(ref["loss"][k])
        out[f"loss{k}"] = (abs(lp - lr) / abs(lr) if math.isfinite(lp)
                           else math.inf)

    def norms(vectors):
        return [np.linalg.norm(np.asarray(v, np.float64)) for v in vectors]

    for name, gaps in (
            ("grad", leaf_gaps(prog["grad"], ref["grad"], keep)),
            ("update", leaf_gaps(prog["update"], ref["update"], keep)),
            ("bn", leaf_gaps(norms(prog["bn"]), norms(ref["bn"]))),
            ("bn_vec", vector_gaps(prog["bn"], ref["bn"]))):
        out[name] = float(np.max(gaps))  # the worst leaf
        out[name + "_med"] = float(np.median(gaps))  # the median leaf
    return out


def _spec_order(job: Job, tree) -> List:
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    by_path = dict(zip(job.paths, leaves))
    spec = job.cell.reference().param_spec(job.cell.model)
    return [by_path[p] for p, _ in spec]


def program_readers(job: Job):
    """Jitted readers of the program's state: after one step, each
    leaf's gradient norm (worked back from the optimizer's delta and m)
    and each BN site's statistics; after three, each leaf's change."""
    import jax
    import jax.numpy as jnp
    cell, ref = job.cell, job.cell.reference()
    r = cell.recipe
    spec = ref.param_spec(cell.model)
    sites = ref.bn_sites(spec)
    eta, a_sgd = ref.schedule(r, 0, cell.global_batch)
    a_rms = (1.0 - a_sgd) * r["eta_rmsprop"] / eta
    wd = [r["weight_decay"] if ref.decays(p, r) else 0.0 for p, _ in spec]

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    @jax.jit
    def after_one(state):
        th = _spec_order(job, state["params"])
        de = _spec_order(job, state["opt"]["delta"])
        mm = _spec_order(job, state["opt"]["m"])
        g = []
        for t, d, m, w in zip(th, de, mm, wd):
            coef = a_sgd + a_rms / (jnp.sqrt(m) + r["eps"])
            g_dec = -d / coef
            g.append(norm(g_dec - w * (t - eta * d)))
        ms = state["model_state"]
        bn = [ms[s][k].astype(jnp.float32).ravel()
              for k in ("mean", "var") for s in sites]
        return jnp.stack(g), bn

    @jax.jit
    def change(params, params0):
        return jnp.stack([norm(a - b) for a, b in zip(
            _spec_order(job, params), _spec_order(job, params0))])

    return after_one, change


def check_steps(job: Job, key, pool, timed: TimedStep, extra: int = 0):
    """Drive the job from fresh weights through its first steps, through
    the trainer and the feed the window uses, reading after step 1 and
    after step 3 what the check compares. ``extra`` more steps follow in
    the same trainer run, until its feed runs steady, to time a step.
    Returns the state after all of them and the program's readings."""
    import jax
    if job.readers is None:
        job.readers = program_readers(job)
    after_one, change = job.readers
    losses, got = [], {}

    def hook(i, out):
        new_state, metrics = out
        if i < CHECK_STEPS:
            losses.append(metrics["loss"])
        if i == 0:
            g, bn = jax.device_get(after_one(
                jax.block_until_ready(new_state)))
            got["grad"], got["bn"] = np.asarray(g), list(bn)
        if i == CHECK_STEPS - 1:
            params = jax.block_until_ready(new_state["params"])
            got["update"] = np.asarray(change(params, job.make_params(key)))

    state = fresh_state(job, key)
    pool.offset = 0
    timed.hook = hook
    try:
        res = run_trainer(job, timed, state, make_source(job, pool),
                          CHECK_STEPS + extra)
    finally:
        timed.hook = None
    got["loss"] = np.asarray([float(x) for x in losses])
    return res.state, got


def steady_step_s(starts: List[float], last: int = 5) -> float:
    """Median of the last ``last`` step intervals."""
    d = np.diff(np.asarray(starts[-(last + 1):]))
    return float(np.median(d)) if len(d) else 1.0


# ----------------------------------------------------------- reference

class Reference:
    """The plain reference over the first three steps: the same weights
    (drawn from the same key), the same pool rows, the augmentation the
    configuration states. ``dtype_name`` is bfloat16 for the control."""

    def __init__(self, cell: Cell, prog_seed: int,
                 dtype_name: str = "float32"):
        import jax
        import jax.numpy as jnp
        ref = cell.reference()
        spec = ref.param_spec(cell.model)
        sites = ref.bn_sites(spec)
        dtype = jnp.dtype(dtype_name)
        gb, aug = cell.global_batch, cell.traffic["augment"]
        kw = dict(model=cell.model, recipe=cell.recipe,
                  eps=cell.config["conventions"]["bn_eps"], global_batch=gb,
                  n_workers=cell.chips)

        def norm(x):
            return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

        def norms(tree):
            return jnp.stack([norm(tree[p]) for p, _ in spec])

        @jax.jit
        def step(p, d, m, k, images, labels):
            x = ref.augment(images, ref.augment_params(
                prog_seed, k, gb, aug["max_shift"]), aug["mean"],
                aug["std"])
            p2, d2, m2, loss, g, stats = ref.train_step(p, d, m, k, x,
                                                        labels, **kw)
            bn = [stats[s][k].astype(jnp.float32).ravel()
                  for k in (0, 1) for s in sites]
            return p2, d2, m2, loss, norms(g), bn

        @jax.jit
        def start(key):
            p = jax.tree.map(lambda x: x.astype(dtype),
                             ref.init_params(key, spec))
            z = jax.tree.map(jnp.zeros_like, p)
            return p, z, z

        @jax.jit
        def change(p, key):
            p0 = ref.init_params(key, spec)
            return norms({q: p[q].astype(jnp.float32) - p0[q] for q in p})

        self._step, self._start, self._change = step, start, change

    def readings(self, key, pool) -> Dict:
        import jax
        p, d, m = self._start(key)
        losses, out = [], {}
        for k in range(CHECK_STEPS):
            rows = pool.rows(k)
            p, d, m, loss, g, bn = self._step(p, d, m, k, rows["images"],
                                              rows["labels"])
            losses.append(loss)
            if k == 0:
                out["grad"] = np.asarray(g)
                out["bn"] = list(jax.device_get(bn))
        out["update"] = np.asarray(self._change(p, key))
        out["loss"] = np.asarray([float(x) for x in losses])
        return out


# -------------------------------------------------------------- window

def step_p95_ms(starts: List[float], end: float) -> float:
    """95th percentile over every step of the window: each step lasts
    from its call to the next call (the trainer syncs on each step's
    loss before the next), the last until the window closes."""
    steps = np.diff(np.asarray(list(starts) + [end]))
    if len(steps) < 2:
        return float(steps[0]) * 1e3
    return statistics.quantiles(steps.tolist(), n=20)[-1] * 1e3


@dataclasses.dataclass
class Window:
    steps: int
    seconds: float
    starts: List[float]
    end: float
    losses: List[Optional[float]]
    input_wait_s: float
    state: Any


def measure(job: Job, timed: TimedStep, state, pool, steps: int,
            trace_dir: Optional[str] = None,
            traced_steps: int = 0) -> Window:
    """The window: ``steps`` steps of ``Trainer.run``; with ``trace_dir``
    its last ``traced_steps`` run under the profiler."""
    import jax
    pool.offset = CHECK_STEPS
    source = make_source(job, pool)
    timed.reset()
    timed.trace_dir = trace_dir
    timed.trace_from = max(0, steps - traced_steps)
    try:
        t0 = time.perf_counter()
        res = run_trainer(job, timed, state, source, steps)
        jax.block_until_ready(res.state)
        t1 = time.perf_counter()
    finally:
        timed.end_trace()
    return Window(steps=timed.calls, seconds=t1 - t0,
                  starts=list(timed.starts), end=t1,
                  losses=[h.get("loss") for h in res.history],
                  input_wait_s=float(res.input_stats.get("data_wait_s", 0.0)),
                  state=res.state)


# ----------------------------------------------------------------- run

def peak_bytes(device) -> int:
    """Peak device memory: the allocator's peak of live buffers plus the
    peak it reserved for the compiled programs' temporaries, which the
    TPU runtime keeps apart from ``peak_bytes_in_use``."""
    stats = device.memory_stats() or {}
    return (int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)))


def make_pool(cell: Cell, key):
    import jax
    from bench.pool import ImagePool
    m = cell.model
    return ImagePool(jax.random.fold_in(key, 1), batch=cell.global_batch,
                     n_batches=cell.traffic["pool_batches"],
                     size=m["image_size"], channels=m["image_channels"],
                     classes=m["num_classes"])


def free(*trees):
    """Release the device buffers of ``trees`` now."""
    import jax
    for t in trees:
        for x in jax.tree_util.tree_leaves(t):
            if isinstance(x, jax.Array) and not x.is_deleted():
                x.delete()


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    cell: Cell
    steps: int
    traced_steps: int  # the window's last steps, which the trace holds
    window_s: float
    images_per_s: float
    input_wait_s: float
    trace: Any
    peaks: Optional[Dict]
    flops_per_image: float


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, log=print) -> Dict:
    """One run of ``cell``; returns the result line's object."""
    from bench import flops as flops_mod
    from bench import peaks as peaks_mod
    from bench import traces

    kind = devices[0].device_kind
    peak = peaks_mod.peaks(kind) if devices[0].platform != "cpu" else None
    key = seed_key(seed)
    job = build_job(cell, devices)
    m = cell.model
    pool = make_pool(cell, key)
    timed = TimedStep(job.train_step)
    log(f"built in {time.perf_counter() - t_start:.2f} s")
    state, prog = check_steps(job, key, pool, timed, CALIBRATION_STEPS)
    step_s = max(steady_step_s(timed.starts), 1e-3)
    steps = max(2, int(round(seconds / step_s)))
    setup_s = time.perf_counter() - t_start

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    traced = min(steps, max(2, int(round(TRACE_SECONDS / step_s))))
    win = measure(job, timed, state, pool, steps, tdir, traced)
    mem = max(peak_bytes(d) for d in devices[:cell.chips])
    log(f"memory stats of the first chip: {devices[0].memory_stats()}")
    free(win.state)

    ref = Reference(cell, job.prog_seed).readings(key, pool)
    numbers = compare(prog, ref)
    checks = {k: {"value": numbers[k], "limit": cell.limits[k]}
              for k in NUMBERS if k in cell.limits}
    correct = bool(checks) and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())

    images_per_s = win.steps * cell.global_batch / win.seconds
    failed = sum(1 for x in win.losses
                 if x is None or not math.isfinite(x))
    result = {"correct": bool(correct), "attempted": win.steps,
              "failed": failed, "metrics": {}}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": cell.chips, "memory_peak_bytes": mem}
    if not trace:
        e2e = {"images_per_s": (images_per_s, "images/s"),
               "step_ms_p95": (step_p95_ms(win.starts, win.end), "ms"),
               "setup_s": (setup_s, "s")}
        for spec in cell.end_to_end:
            v, unit = e2e[spec["name"]]
            result["metrics"][spec["name"]] = {"value": v, "unit": unit}
    else:
        tr = traces.load_xplane(tdir)
        ctx = Context(cell=cell, steps=win.steps, traced_steps=traced,
                      window_s=win.seconds,
                      images_per_s=images_per_s,
                      input_wait_s=win.input_wait_s, trace=tr, peaks=peak,
                      flops_per_image=flops_mod.train_flops_per_image(m))
        for spec in cell.per_layer:
            reader = load_module(os.path.join(cell.bench_dir, "metrics",
                                              spec["name"] + ".py"),
                                 "bench_metric_" + spec["name"])
            v = reader.read(ctx)
            if v is not None:
                result["metrics"][spec["name"]] = {"value": v,
                                                   "unit": spec["unit"]}
        device["busy_s"] = traces.busy_s(tr)
        device["window_s"] = traces.window_s(tr)
        result["breakdown"] = {"device_ops": traces.top_ops(tr),
                               "idle_gaps": traces.idle_gaps(tr)}
        shutil.rmtree(tdir, ignore_errors=True)
    result["device"] = device
    log(f"window: {win.steps} steps in {win.seconds:.3f} s, "
        f"setup {setup_s:.2f} s, losses {prog['loss'].tolist()} "
        f"(program) {ref['loss'].tolist()} (reference)")
    log(window_text(win))
    result["checks"] = checks
    return result


def window_text(win: Window) -> str:
    """Where the window's time went: before the first step's call, the
    steps (median and longest), and the last step to the close."""
    steps = np.diff(np.asarray(win.starts + [win.end]))
    lead = win.starts[0] - (win.end - win.seconds) if win.starts else 0.0
    i = int(np.argmax(steps)) if len(steps) else 0
    return (f"window time: {lead:.3f} s before the first step, steps "
            f"sum {steps.sum():.3f} s, median {np.median(steps) * 1e3:.2f} "
            f"ms, longest {steps.max() * 1e3:.2f} ms (step {i}), the "
            f"last step to the close {steps[-1] * 1e3:.2f} ms")


def checks_text(checks: Dict[str, Dict]) -> List[str]:
    return [f"check {k}: {c['value']:.6g} limit {c['limit']}"
            for k, c in checks.items()]

"""Runner for training cells: the window is ONE call to the public ``fit``.

Set-up builds the net, installs the benchmark's weights, and drives the
first ``check_steps`` steps through the same ``fit`` and the same kind of
iterator the window uses (they compile the step and are what ``correct``
compares). The window hands that same net a time-bounded iterator. After
the window has closed and the program is freed, the plain reference
follows the same first steps from the same weights.
"""

import gc
import math
import resource
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_train
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import DataSetIterator

TRACE_SLICE_S = 3.0   # a traced run reduces the last seconds of its window
TRACE_SETTLE_S = 1.5  # and starts the profiler this long before them


class Feed(DataSetIterator):
    """The benchmark's iterator: cycles host batches until a step count or a
    deadline. In a traced run it wraps each ``next`` in a ``feed``
    annotation and drives the ``Tracer``."""

    def __init__(self, datasets, steps=None, deadline=None, tracer=None):
        self.datasets, self.steps, self.deadline, self.tracer = datasets, steps, deadline, tracer
        self.count = 0

    def reset(self):
        pass  # one pass: fit resets before it iterates, the count must survive

    def batch(self):
        return len(self.datasets[0])

    def has_next(self):
        if self.steps is not None:
            return self.count < self.steps
        return time.perf_counter() < self.deadline

    def next(self):
        if self.tracer is None:
            ds = self.datasets[self.count % len(self.datasets)]
        else:
            self.tracer.tick(self.deadline)
            with jax.profiler.TraceAnnotation("feed"):
                ds = self.datasets[self.count % len(self.datasets)]
        self.count += 1
        return ds


class Tracer:
    """Starts ``jax.profiler`` late in the window from the iterator's own
    thread (no thread of its own, no hook in the program), lets its start-up
    settle, then drops a ``measure`` mark into the trace: the traced window
    runs from that mark to the window's end."""

    def __init__(self, log_dir):
        self.log_dir, self.started, self.marked_at, self.steps_at_mark = log_dir, False, None, None

    def tick(self, deadline):
        now = time.perf_counter()
        if not self.started and now >= deadline - TRACE_SLICE_S - TRACE_SETTLE_S:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1  # annotations only: at 2 every chunk of a host copy is an event
            jax.profiler.start_trace(self.log_dir, profiler_options=options)
            self.started = True
        elif self.started and self.marked_at is None and now >= deadline - TRACE_SLICE_S:
            with jax.profiler.TraceAnnotation("measure"):
                self.marked_at = time.perf_counter()

    def stop(self, window_end):
        """Seconds from the mark to the window's end; writing the trace out
        happens after it and is no part of either."""
        if not self.started:
            return None
        jax.profiler.stop_trace()
        return None if self.marked_at is None else window_end - self.marked_at


def memory_peak(device) -> int:
    """Peak bytes held on one chip: the arrays in use at their peak plus what
    the runtime reserved for the loaded programs' temporaries, which
    ``peak_bytes_in_use`` leaves out on a TPU (PERF.md section 2)."""
    stats = device.memory_stats() or {}
    print(f"memory_stats of {device}: {stats}", flush=True)
    return stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)


def _datasets(host_batches):
    return [DataSet(x, y, features_mask=m) for x, y, m in host_batches]


def _check_same_tree(ours, theirs, what):
    shapes = lambda t: jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), t)
    if shapes(ours) != shapes(theirs):
        raise RuntimeError(f"{what}: the benchmark's tree differs from the program's:\n"
                           f"{shapes(ours)}\n{shapes(theirs)}")


def _first_moment(opt_state, params, attr):
    """The optimizer's first-moment tree (``mu`` for Adam, ``trace`` for
    momentum), picked out of the program's optimizer state by path and
    returned in the layout of ``params``."""
    found = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(opt_state)[0]:
        names = [getattr(k, "name", None) for k in path]
        if attr in names:
            tail = path[names.index(attr) + 1:]
            found[tuple(k.key for k in tail)] = leaf
    flat = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(flat[1], [found[tuple(k.key for k in p)] for p, _ in flat[0]])


def setup(ctx):
    """Build, install weights, make batches. Returns (net, fitter, datasets)."""
    config, traffic, family = ctx.config, ctx.traffic, ctx.family
    from deeplearning4j_tpu.runtime.environment import get_environment
    # "bfloat16" is what allow_bfloat16() sets: bf16 compute, f32 parameters and moments
    get_environment().set_compute_dtype(config["precision"]["compute"])
    net = family.build(config, ctx.seed)
    params, state = family.init_params(config, ctx.seed)
    _check_same_tree(params, net.train_state.params, "parameters")
    _check_same_tree(state, net.train_state.model_state, "model state")
    net.set_params(params)
    fitter = net
    if traffic.get("workers", 1) > 1:
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        fitter = ParallelWrapper.builder(net).workers(traffic["workers"]).build()
    return net, fitter, _datasets(family.batches(config, traffic, ctx.seed))


def program_readings(ctx, net, fitter, datasets):
    """The program's first steps, through the window's own call and feed:
    each step's loss, the first gradient as the optimizer got it (from
    its first moment after one step), the change after the last."""
    opt = ctx.config["optimizer"]
    attr, scale = {"adam": ("mu", 1.0 / (1.0 - opt.get("b1", 0.0))), "nesterov": ("trace", 1.0)}[opt["name"]]
    losses, grad = [], None
    for i in range(ctx.traffic["check_steps"]):
        fitter.fit(Feed(datasets[i:i + 1], steps=1))
        losses.append(float(net.score()))
        if grad is None:
            # kept on the host: the window's memory peak is the program's alone
            moment = _first_moment(net.train_state.opt_state, net.train_state.params, attr)
            grad = jax.tree.map(lambda m: scale * np.asarray(m), jax.device_get(moment))
            del moment
    start = ctx.family.init_params(ctx.config, ctx.seed)
    ts = net.train_state
    delta = reference_train.change((ts.params, ts.model_state), start)  # taken on the device a leaf at a time
    del start
    return {"losses": losses, "grad": grad, "delta": delta}


def reference_readings(ctx, precision="float32", transform=None, steps=None):
    """The plain reference's readings, as host arrays (``follow`` works in
    place: on the device it costs the parameters, the moments and one
    gradient, 16 bytes a parameter under Adam, plus a step's activations)."""
    start = ctx.family.init_params(ctx.config, ctx.seed)
    host = ctx.family.batches(ctx.config, ctx.traffic, ctx.seed)[:steps or ctx.traffic["check_steps"]]
    device = [tuple(None if a is None else jnp.asarray(a) for a in b) for b in host]
    return reference_train.follow(ctx.family.reference_loss(ctx.config), start[0], start[1],
                                  device, ctx.config["optimizer"], precision, transform)


def reference(ctx):
    """What the program is held against: the float32 reference's readings and,
    where the configuration computes in a narrower type, the reference's first
    gradient with its operands rounded to that type (the yardstick of
    ``grad_diff_roundings``)."""
    want = reference_readings(ctx)
    compute = ctx.config["precision"]["compute"]
    if compute != "float32":
        want["grad_rounded"] = reference_readings(ctx, precision=compute, steps=1)["grad"]
    return want


def run(ctx) -> dict:
    from deeplearning4j_tpu.runtime import compile_cache

    def mark(what):
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20
        print(f"{what} at {time.time() - ctx.process_start:.2f} s (host peak {rss:.2f} GiB)", flush=True)

    mark("imports done")
    net, fitter, datasets = setup(ctx)
    jax.block_until_ready(net.train_state)
    mark("net built, weights installed, batches made")
    got = program_readings(ctx, net, fitter, datasets)
    jax.block_until_ready(net.train_state)
    mark(f"{ctx.traffic['check_steps']} first steps driven through fit")

    tracer = Tracer(ctx.trace_dir) if ctx.trace else None
    profiler = None
    if ctx.trace:
        from deeplearning4j_tpu.train.profiler import TrainingProfiler
        profiler = TrainingProfiler()
    before = compile_cache.stats()
    t0 = time.perf_counter()
    setup_s = time.time() - ctx.process_start
    feed = Feed(datasets, deadline=t0 + ctx.seconds, tracer=tracer)
    fitter.fit(feed, profiler=profiler)  # the window: one call to the public fit
    jax.block_until_ready(net.train_state)
    window_s = time.perf_counter() - t0
    mark(f"window closed after {window_s:.3f} s")
    traced_s = tracer.stop(t0 + window_s) if tracer else None
    if tracer:
        mark("trace written")
    after = compile_cache.stats()
    last_loss = float(net.score())
    steps = feed.count
    peak = max(memory_peak(d) for d in jax.local_devices())
    # a traced run joins each device op with its scope through the step program's optimized HLO
    # text, which goes with the net: read it off the AOT executables first
    hlo_texts = [text for cache in net._jit_cache.values() if hasattr(cache, "hlo_texts")
                 for text in cache.hlo_texts()] if ctx.trace else None

    # the program goes before the reference comes: its peak has been read,
    # its state is freed
    del net, fitter, feed
    gc.collect()
    mark("program freed")
    checks = reference_train.compare(got, reference(ctx))
    mark("reference followed and compared")
    samples = steps * ctx.family.samples_per_step(ctx.traffic)
    return {
        "end_to_end": {"train_samples_per_s": samples / window_s, "setup_s": setup_s},
        "attempted": steps, "failed": 0 if math.isfinite(last_loss) else steps,
        "checks": checks, "memory_peak_bytes": peak,
        "steps": steps, "window_s": window_s, "traced_s": traced_s, "hlo_texts": hlo_texts,
        "profiler": profiler.report() if profiler else None,
        "compiles_in_window": sum(after[k] - before[k] for k in ("hits", "misses", "corrupt_entries")),
    }

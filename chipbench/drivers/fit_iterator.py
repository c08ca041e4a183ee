"""Driver ``fit_iterator``: one ``net.fit(iterator)`` call over host batches.

The window is what a training user runs: a ``DataSetIterator`` of the
benchmark's own hands ``net.fit`` uint8 NCHW host batches until
``--seconds`` are up, the [0, 255] -> [0, 1] scaling runs inside the
compiled step (``DeviceAugmentation().scale_to``), and the clock stops
after ``block_until_ready`` on the parameters when ``fit`` returns.

Set-up drives the same net through its first ``check_steps`` updates with
the same call and feed, on the pool's first batches, and keeps what
``chipbench.compare`` needs of them: each step's loss, the first gradient
as Adam got it (its first moment after step one, on the host), and the norm
of every leaf's change after the last. The net then goes on into the
window.

A traffic file names this driver and gives ``batch``, ``pool`` (distinct
host batches from the seed, cycled in a seeded order), ``check_steps`` and
``trace_steps`` (how many steady steps a ``--trace 1`` run profiles).
"""

import gc
import math
import time

import numpy as np

from chipbench import compare, refnn, trace as trace_mod
from chipbench.weights import make_weights


# ------------------------------------------------------------------ traffic
def _onehot_labels(rng, cfg, batch):
    idx = rng.integers(0, cfg["num_classes"], batch)
    return np.eye(cfg["num_classes"], dtype=np.float32)[idx]


def _yolo_grid_labels(rng, cfg, batch):
    """DL4J's ``[B, 4+C, gridH, gridW]``: the box (x1, y1, x2, y2, grid
    units) and a one-hot class in the cell that holds the box's centre."""
    n_cls = cfg["num_classes"]
    grid = cfg["input_shape"][1] // 32
    lo, hi = cfg["boxes_per_image"]
    y = np.zeros((batch, 4 + n_cls, grid, grid), np.float32)
    for b in range(batch):
        for _ in range(int(rng.integers(lo, hi + 1))):
            cx, cy = rng.uniform(0.0, grid, 2)
            w, h = np.exp(rng.uniform(np.log(0.5), np.log(grid * 0.8), 2))
            col, row = int(cx), int(cy)
            if y[b, 4:, row, col].any():
                continue            # one object a cell: the first stays
            x1, x2 = max(cx - w / 2, 0.0), min(cx + w / 2, float(grid))
            y1, y2 = max(cy - h / 2, 0.0), min(cy + h / 2, float(grid))
            y[b, 0:4, row, col] = (x1, y1, x2, y2)
            y[b, 4 + int(rng.integers(0, n_cls)), row, col] = 1.0
    return y


LABELS = {"onehot": _onehot_labels, "yolo_grid": _yolo_grid_labels}


def make_batches(cfg, traffic, seed):
    """The pool: ``traffic["pool"]`` distinct (uint8 images, float32
    labels) host batches from the seed; every row differs."""
    rng = np.random.default_rng([int(seed), 0xC41B])
    batch = int(traffic["batch"])
    shape = (batch,) + tuple(cfg["input_shape"])
    labels = LABELS[cfg["labels"]]
    return [(rng.integers(0, 256, shape, dtype=np.uint8),
             labels(rng, cfg, batch)) for _ in range(int(traffic["pool"]))]


def window_order(n_pool, seed):
    """The seeded order in which the window cycles the pool: every seed
    sends the same batches' worth of work, in another order."""
    return np.random.default_rng([int(seed), 0x0DE2]).permutation(n_pool)


# ----------------------------------------------------- iterator and listener
def make_iterator(batches, order, seconds=None, limit=None, hold=None):
    """A ``DataSetIterator`` over host batches in ``order`` (cycled) whose
    ``hasNext()`` turns false after ``limit`` batches or once ``seconds``
    have passed since ``reset()``, but not while ``hold()`` is true (a
    traced run's profile is still open: starting and stopping the profiler
    takes seconds on four chips). Every pull is a host span."""
    import jax
    from deeplearning4j_tpu.data.dataset import DataSet, DataSetIterator

    class DeadlineIterator(DataSetIterator):
        def __init__(self):
            self.pulled = 0
            self.started = None
            self.reset()

        def reset(self):
            self.pulled = 0
            self.started = time.perf_counter()

        def hasNext(self):
            if limit is not None and self.pulled >= limit:
                return False
            if hold is not None and hold():
                return True
            return seconds is None or \
                time.perf_counter() - self.started < seconds

        def next(self):
            with jax.profiler.TraceAnnotation(trace_mod.PULL_SPAN):
                x, y = batches[int(order[self.pulled % len(order)])]
                self.pulled += 1
                return DataSet(x, y)

        def batch(self):
            return int(batches[0][0].shape[0])

    return DeadlineIterator()


class StepListener:
    """Counts update steps, opens a host span around each, keeps the loss
    of the steps it is told to keep, runs ``at_step`` callbacks, and
    profiles the stretch of steps a traced run asks for."""

    def __init__(self):
        self.steps = 0
        self.losses = []
        self.keep_losses = False
        self.at_step = {}           # step count -> callback(net)
        self.trace = None           # (first, n_steps, directory) or None
        self.traced = None          # (t_start, t_stop, first, last) host
        self._span = None

    def onIterationStart(self, net, iteration):
        import jax
        if self.trace and self.steps == self.trace[0]:
            jax.block_until_ready(net._params)
            jax.profiler.start_trace(self.trace[2])
            self._t_trace = time.perf_counter()
        self._span = jax.profiler.StepTraceAnnotation(
            trace_mod.STEP_SPAN, step_num=self.steps)
        self._span.__enter__()

    def iterationDone(self, net, iteration, epoch):
        import jax
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        self.steps += 1
        if self.keep_losses:
            self.losses.append(net._score)
        cb = self.at_step.pop(self.steps, None)
        if cb is not None:
            cb(net)
        if self.trace and self.steps == self.trace[0] + self.trace[1]:
            jax.block_until_ready(net._params)
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.traced = (self._t_trace, t1, self.trace[0], self.steps)
            self.trace = None


def _change_norms(after, before):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a}
    return {k: float(v) for k, v in norms(after, before).items()}


# -------------------------------------------------------------- the program
def configure(net, cfg):
    """Set the net as the configuration's ``settings`` say."""
    s = cfg["settings"]
    if s.get("precision"):
        net.setPrecisionPolicy(s["precision"])
    if s.get("compute_layout"):
        net.setComputeLayout(s["compute_layout"])
    if s.get("epilogue_fusion"):
        net.setEpilogueFusion(True)
    return net


def fit_call(net, iterator):
    """The call the window times, and set-up's first steps go through."""
    from deeplearning4j_tpu.nn.augment import DeviceAugmentation
    net.fit(iterator, augment=DeviceAugmentation().scale_to(0.0, 1.0))


def fit_of(model):
    """A configuration whose program is not driven by ``net.fit`` (a
    trainer over several chips) brings a ``fit(net, iterator)`` of its
    own in its ``model.py``."""
    return getattr(model, "fit", fit_call)


def first_steps(net, model, cfg, traffic, batches, seed, listener, fit):
    """Drive ``net`` through its first ``check_steps`` updates with the
    window's own call and feed; return what the comparison needs of them."""
    import jax
    n = int(traffic["check_steps"])
    got = {}
    beta1 = cfg["updater"]["beta1"]

    def after_first(net_):
        # Adam's first moment after one step is (1 - beta1) times the
        # gradient it was handed; taken to the host before the next step
        # donates it
        m = jax.device_get(model.read_leaves(net_, "m"))
        got["first_grads"] = {k: v / (1.0 - beta1) for k, v in m.items()}

    listener.keep_losses = True
    listener.at_step[listener.steps + 1] = after_first
    fit(net, make_iterator(batches, np.arange(n), limit=n))
    listener.keep_losses = False
    jax.block_until_ready(net._params)
    got["losses"] = [float(v) for v in listener.losses[-n:]]
    start = make_weights(model.param_spec(cfg), seed)
    got["change_norms"] = _change_norms(model.read_leaves(net, "params"),
                                        start)
    return got


def run(cell, args, clock0, interpret_kernels=False, fit=None):
    """One run of a cell: set-up, the window, and what was measured.

    ``cell`` carries ``cfg``, ``model`` (the configuration's module),
    ``traffic`` and ``chips``. Returns a dict the metric readers and
    ``chipbench.compare`` take their numbers from; ``net`` is dropped
    before it returns.
    """
    import jax
    cfg, model, traffic = cell["cfg"], cell["model"], cell["traffic"]
    fit = fit or fit_of(model)
    reach_chip_s = getattr(args, "reach_chip_s", 0.0)
    phases = {"reach_chip": reach_chip_s,
              "to_driver": time.perf_counter() - clock0 - reach_chip_s}
    mark = [time.perf_counter()]

    def phase(name):
        now = time.perf_counter()
        phases[name] = now - mark[0]
        mark[0] = now

    if cfg["settings"].get("pallas_overrides"):
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        pk.install_platform_overrides(interpret=interpret_kernels)

    if int(traffic["pool"]) < int(traffic["check_steps"]):
        raise ValueError("the traffic's pool is smaller than check_steps: "
                         "the first steps need rows that all differ")
    batches = make_batches(cfg, traffic, args.seed)
    phase("host_batches")
    weights = jax.block_until_ready(
        make_weights(model.param_spec(cfg), args.seed))
    phase("weights")
    net = configure(model.build(cfg, weights, chips=int(cell["chips"])), cfg)
    del weights
    phase("build_net")
    listener = StepListener()
    net.setListeners(listener)
    checked = first_steps(net, model, cfg, traffic, batches, args.seed,
                          listener, fit)
    phase("first_steps")

    counters = trace_mod.ProgramCounters(traced=bool(args.trace))
    if args.trace:
        n_check = int(traffic["check_steps"])
        listener.trace = (n_check + int(traffic["trace_after_steps"]),
                          int(traffic["trace_steps"]), args.trace_dir)
    order = window_order(len(batches), args.seed)
    iterator = make_iterator(batches, order, seconds=args.seconds,
                             hold=lambda: listener.trace is not None)
    steps_before = listener.steps
    counters.start()
    setup_s = time.perf_counter() - clock0 - reach_chip_s
    t0 = time.perf_counter()
    fit(net, iterator)
    jax.block_until_ready(net._params)
    window_s = time.perf_counter() - t0
    counters.stop()
    steps = listener.steps - steps_before
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    final_loss = float(net._score)
    net.setListeners()
    del net, iterator
    gc.collect()    # a trainer and its net point at each other
    return {"setup_s": setup_s, "window_s": window_s, "steps": steps,
            "attempted": steps,
            "failed": 0 if math.isfinite(final_loss) else steps,
            "batch": int(traffic["batch"]), "chips": int(cell["chips"]),
            "memory_stats": stats, "checked": checked, "batches": batches,
            "final_loss": final_loss, "counters": counters.read(),
            "traced": listener.traced, "phases": phases}


# ----------------------------------------------------------- the comparison
def reference_numbers(cell, batches, seed, precision="f32", rows=None):
    """The plain reference over the first ``check_steps`` update steps,
    from the same weights and batches, as ``compare.numbers`` wants it."""
    import jax
    cfg, model = cell["cfg"], cell["model"]
    n = int(cell["traffic"]["check_steps"])
    hp = {k: v for k, v in cfg["updater"].items() if k != "kind"}
    chips = int(cell["chips"])
    if "loss" not in cell:      # one loss a cell, so its jitted step is kept
        cell["loss"] = cell["reference"].make_loss(cfg)
    ref = refnn.train_steps(
        cell["loss"], make_weights(model.param_spec(cfg), seed), batches[:n], hp,
        precision, rows,
        devices=jax.devices()[:chips] if chips > 1 else None)
    ref["change_norms"] = _change_norms(
        ref.pop("params"), make_weights(model.param_spec(cfg), seed))
    ref["first_grads"] = jax.device_get(ref["first_grads"])
    return ref


def check(cell, result, seed):
    """(correct, checks): what set-up's first steps gave against the plain
    reference, each number beside its limit from the cell's file. Called
    once the window has closed, the peak has been read and the net is
    gone."""
    reference = reference_numbers(cell, result["batches"], seed)
    correct, checks = compare.judge(
        compare.numbers(result["checked"], reference), cell["limits"])
    checks["final_loss"] = result["final_loss"]
    return correct, checks

"""Driver ``fit_tokens``: one ``net.fit(iterator)`` call over host batches
of token ids.

The window is what someone pre-training a language model runs: a
``DataSetIterator`` of the benchmark's own hands ``net.fit`` int32 batches
``[batch, seq_len]`` of token ids with the next tokens as int32 labels
(each row drawn as ``seq_len + 1`` ids, uniform in the configuration's
vocabulary, from the seed) until ``--seconds`` are up; nothing is
augmented; the clock stops after ``block_until_ready`` on the parameters
when ``fit`` returns. ``batch`` in what it returns is sequences a step, so
``img_per_s_per_chip`` reads sequences a second a chip.

Everything else is ``fit_iterator``'s, imported from it: the deadline
iterator, the step listener, set-up's first ``check_steps`` updates
through the window's own call and feed, and the comparison with the plain
reference that decides ``correct``.
"""

import gc
import math
import time

import numpy as np

from chipbench import trace as trace_mod
from chipbench.drivers import fit_iterator as base
from chipbench.weights import make_weights

check = base.check
reference_numbers = base.reference_numbers


def make_batches(cfg, traffic, seed):
    """The pool: ``traffic["pool"]`` distinct (tokens, next tokens) int32
    host batches from the seed."""
    rng = np.random.default_rng([int(seed), 0x70CE])
    batch, s = int(traffic["batch"]), int(traffic["seq_len"])
    if s != int(cfg["seq_len"]):
        raise ValueError(f"the traffic's seq_len {s} is not the "
                         f"configuration's {cfg['seq_len']}")
    out = []
    for _ in range(int(traffic["pool"])):
        rows = rng.integers(0, cfg["vocab_size"], (batch, s + 1),
                            dtype=np.int32)
        out.append((np.ascontiguousarray(rows[:, :-1]),
                    np.ascontiguousarray(rows[:, 1:])))
    return out


def fit_call(net, iterator):
    """The call the window times, and set-up's first steps go through."""
    net.fit(iterator)


def run(cell, args, clock0, interpret_kernels=False, fit=None):
    """One run of a cell, as ``fit_iterator.run`` makes it, over token
    batches."""
    import jax
    # a tree without the loop construct fails here, before any weights
    from deeplearning4j_tpu.nn.graph import LoopVertex  # noqa: F401
    cfg, model, traffic = cell["cfg"], cell["model"], cell["traffic"]
    fit = fit or fit_call
    reach_chip_s = getattr(args, "reach_chip_s", 0.0)
    phases = {"reach_chip": reach_chip_s,
              "to_driver": time.perf_counter() - clock0 - reach_chip_s}
    mark = [time.perf_counter()]

    def phase(name):
        now = time.perf_counter()
        phases[name] = now - mark[0]
        mark[0] = now

    if int(traffic["pool"]) < int(traffic["check_steps"]):
        raise ValueError("the traffic's pool is smaller than check_steps: "
                         "the first steps need rows that all differ")
    batches = make_batches(cfg, traffic, args.seed)
    phase("host_batches")
    weights = jax.block_until_ready(
        make_weights(model.param_spec(cfg), args.seed))
    phase("weights")
    net = base.configure(
        model.build(cfg, weights, chips=int(cell["chips"])), cfg)
    del weights
    phase("build_net")
    listener = base.StepListener()
    net.setListeners(listener)
    checked = base.first_steps(net, model, cfg, traffic, batches, args.seed,
                               listener, fit)
    phase("first_steps")

    counters = trace_mod.ProgramCounters(traced=bool(args.trace))
    if args.trace:
        n_check = int(traffic["check_steps"])
        listener.trace = (n_check + int(traffic["trace_after_steps"]),
                          int(traffic["trace_steps"]), args.trace_dir)
    order = base.window_order(len(batches), args.seed)
    iterator = base.make_iterator(batches, order, seconds=args.seconds,
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
    gc.collect()
    return {"setup_s": setup_s, "window_s": window_s, "steps": steps,
            "attempted": steps,
            "failed": 0 if math.isfinite(final_loss) else steps,
            "batch": int(traffic["batch"]), "chips": int(cell["chips"]),
            "memory_stats": stats, "checked": checked, "batches": batches,
            "final_loss": final_loss, "counters": counters.read(),
            "traced": listener.traced, "phases": phases}

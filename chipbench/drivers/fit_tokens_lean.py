"""Driver ``fit_tokens_lean``: ``fit_tokens``' window for a model whose
set-up and reference would not fit the chip as ``fit_tokens`` makes them.

The window, the feed, the listener, the first ``check_steps`` updates
through the window's own call and the result's keys are ``fit_tokens``' and
``fit_iterator``'s, by import. What differs is what is HELD while they
run, at 0.9 B parameters on a 15.75 GiB chip:

- weights are made a leaf at a time from the seed (``make_weights``: a
  key a leaf, ``fold_in(seed key, leaf index)``), not sliced out of one
  flat normal draw (two copies of every parameter alive);
- the program's change norms are taken a leaf at a time against the
  starting leaf made again, not against a second whole set of weights
  beside the net and Adam's state;
- the reference's update steps hold the parameters and ONE set of
  gradients on the device: the gradients go to the host after each step
  (the first step's are what the comparison reads), Adam's moments live
  on the host and pass through the device a leaf at a time
  (``refnn.adam_step`` on one-leaf dicts: the same arithmetic);
- a configuration may have layer states that the seed fixes
  (``model.state_spec``: selection biases with no gradient); the net is
  built with them and the reference's loss is handed them beside the
  parameters;
- a configuration with sparse experts has the program keep which experts
  each token took at each of the first steps (``model.read_selected``);
  the reference follows that choice (``forced=``) and reports how it
  sits in its own float32 scores: ``route_flip_share``, the share of the
  chosen (token, expert) pairs that are not among the reference's own,
  and ``route_worst_margin``, how far the worst of them lies below the
  line between the reference's k-th and next score. Both join
  ``compare.numbers``' and the cell's limits hold them: rounding moves a
  choice across a line it lies on, a fault moves it anywhere;
- a leaf whose reference gradient lies at Adam's eps is left out of the
  change norms that ``compare.numbers`` reads (``step_size_share``): its
  first step follows the SIZE of a gradient that is nought to rounding,
  where every other leaf's follows the sign. The rule reads the
  reference's gradient alone;
- ``grad_routed_gap``, the widest gap between the two norms of a routed
  expert's gradient (``model.routed_leaves``), is shown beside them:
  rounding is incoherent over an expert's millions of elements and
  leaves the norm alone (0.003 on the chip), a wrong gate scales it
  (the selection bias in the gates: 0.04, which no held number sees
  under bfloat16's limits). Not held until its range over seeds is
  known (PERF.md section 7).

``compare.numbers`` and ``compare.judge`` are used as they are. A tree
without the sparse-expert layer fails at the import below, before any
weights are made.
"""

import functools
import gc
import math
import time

import numpy as np

from chipbench import compare, refnn, trace as trace_mod
from chipbench.drivers import fit_iterator as base
from chipbench.drivers import fit_tokens
from chipbench.weights import seed_key

make_batches = fit_tokens.make_batches
fit_call = fit_tokens.fit_call


# ------------------------------------------------------------------ weights
@functools.lru_cache(maxsize=None)
def _leaf_maker(shape, kind, fan_in):
    import jax
    import jax.numpy as jnp

    def make(key):
        z = jax.random.normal(key, shape, jnp.float32)
        if kind == "he":
            return z * (2.0 / fan_in) ** 0.5
        if kind == "gamma":
            return 1.0 + 0.1 * z
        if kind == "small":
            return 0.1 * z
        if kind == "alpha":
            return jnp.full(shape, 0.01, jnp.float32)
        if kind == "near_identity":
            return 3.0 * jnp.eye(shape[0], shape[1], dtype=jnp.float32) \
                + 0.1 * z
        raise ValueError(f"unknown init kind {kind!r}")
    return jax.jit(make)


def make_leaf(spec, seed, i):
    """Leaf ``i`` of ``spec`` from the seed, on the default device."""
    import jax
    _name, shape, kind, fan_in = spec[i]
    return _leaf_maker(tuple(shape), kind, fan_in)(
        jax.random.fold_in(seed_key(seed), i))


def make_weights(spec, seed, offset=0):
    """``{name: float32 array}``, a leaf at a time; ``offset`` moves the
    keys on (the states' leaves follow the parameters')."""
    shifted = [None] * offset + list(spec)
    return {spec[i][0]: make_leaf(shifted, seed, i + offset)
            for i in range(len(spec))}


def make_states(model, cfg, seed):
    spec = getattr(model, "state_spec", lambda cfg: [])(cfg)
    return make_weights(spec, seed, offset=len(model.param_spec(cfg)))


@functools.lru_cache(maxsize=None)
def _change_norm():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))


def change_norms(after, spec, seed):
    """``{leaf: |after - start|}`` with each starting leaf made again."""
    return {name: float(_change_norm()(after[name], make_leaf(spec, seed, i)))
            for i, (name, _s, _k, _f) in enumerate(spec)}


# -------------------------------------------------------------- the program
def first_steps(net, model, cfg, traffic, batches, seed, listener, fit):
    """``fit_iterator.first_steps`` with the change norms taken a leaf at
    a time."""
    import jax
    n = int(traffic["check_steps"])
    got = {}
    beta1 = cfg["updater"]["beta1"]

    selected = getattr(model, "read_selected", lambda net_: {})
    got["selected"] = []

    def after_first(net_):
        m = jax.device_get(model.read_leaves(net_, "m"))
        got["first_grads"] = {k: v / (1.0 - beta1) for k, v in m.items()}

    def after_each(net_, first=False):
        if first:
            after_first(net_)
        got["selected"].append(jax.device_get(selected(net_)))

    listener.keep_losses = True
    for i in range(n):
        listener.at_step[listener.steps + 1 + i] = \
            (lambda net_, first=(i == 0): after_each(net_, first))
    fit(net, base.make_iterator(batches, np.arange(n), limit=n))
    listener.keep_losses = False
    jax.block_until_ready(net._params)
    got["losses"] = [float(v) for v in listener.losses[-n:]]
    got["change_norms"] = change_norms(model.read_leaves(net, "params"),
                                       model.param_spec(cfg), seed)
    got["routed_leaves"] = getattr(model, "routed_leaves",
                                   lambda cfg_: [])(cfg)
    return got


def run(cell, args, clock0, interpret_kernels=False, fit=None):
    """One run of a cell, as ``fit_tokens.run`` makes it."""
    import jax
    # a tree without the expert layer fails here, before any weights
    from deeplearning4j_tpu.nn.layers import SparseExpertsLayer  # noqa: F401
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
    states = make_states(model, cfg, args.seed)
    phase("weights")
    net = base.configure(model.build(cfg, weights, chips=int(cell["chips"]),
                                     states=states,
                                     batch=int(traffic["batch"])), cfg)
    del weights, states
    phase("build_net")
    listener = base.StepListener()
    net.setListeners(listener)
    checked = first_steps(net, model, cfg, traffic, batches, args.seed,
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


# ----------------------------------------------------------- the comparison
#: a leaf stays in the change norms while less than this share of its first
#: Adam step follows the size of its gradient (``step_size_share``)
SIZE_LED_SHARE = 0.02


@functools.lru_cache(maxsize=None)
def _size_share(e1):
    import jax
    import jax.numpy as jnp

    def share(g):
        a = jnp.abs(g) / (jnp.abs(g) + e1)
        return jnp.sum(a * a * (1.0 - a)) / jnp.maximum(jnp.sum(a * a), 1e-30)
    return jax.jit(share)


def step_size_share(g, hp):
    """How far a leaf's first Adam step follows the SIZE of its gradient
    ``g``. The first step of an element is ``lr g / (|g| + e1)``, ``e1 =
    eps / sqrt(1 - beta2)`` (``refnn.adam_step`` at ``t = 1``): where
    ``|g|`` is far above ``e1`` it is ``lr sign(g)`` and no rounding of
    ``g`` moves it; where ``|g|`` lies at ``e1`` it is proportional to
    ``g``, and a program whose gradient differs by bfloat16's rounding
    moves such a leaf otherwise. The number is the first-order change of
    the step's norm for a relative change of every ``g``: ``sum a^2 (1 -
    a) / sum a^2`` with ``a = |g| / (|g| + e1)``; 0 for a sign step (and
    for a leaf with no gradient at all, which does not move), towards 1
    where the whole leaf lies under ``e1``."""
    hp = dict(hp)
    return float(_size_share(hp["eps"] / math.sqrt(1.0 - hp["beta2"]))(g))


def _reference_step(cell, precision, fault):
    """The jitted ``(loss, gradients)`` of the reference, one a precision
    and a planted fault, kept with the cell."""
    import jax
    key = ("lean_reference", precision, fault)
    if key not in cell:
        loss = cell["reference"].make_loss(cell["cfg"], fault=fault)
        ops = refnn.Ops(precision)
        cell[key] = jax.jit(jax.value_and_grad(
            lambda p, s, x, y, forced: loss(p, s, x, y, ops, forced),
            has_aux=True))
    return cell[key]


@functools.lru_cache(maxsize=None)
def _adam_leaf(hp_items):
    import jax
    hp = dict(hp_items)

    def step(p, g, m, v, t):
        new_p, new_m, new_v = refnn.adam_step(
            {"w": p}, {"w": g}, {"w": m}, {"w": v}, t, hp)
        return new_p["w"], new_m["w"], new_v["w"]
    return jax.jit(step, donate_argnums=(0,))


def reference_numbers(cell, batches, seed, precision="f32", fault=None,
                      selected=None):
    """The plain reference over the first ``check_steps`` update steps,
    from the same weights, states and batches, as ``compare.numbers``
    wants it; at most the parameters and one set of gradients on the
    device. ``selected``: the program's expert choice at each step, which
    the reference then follows; ``routing`` in what it returns says how
    that choice sits in the reference's own scores; ``size_led``: each
    leaf's ``step_size_share`` from the first gradient."""
    import jax
    import jax.numpy as jnp
    cfg, model = cell["cfg"], cell["model"]
    n = int(cell["traffic"]["check_steps"])
    hp = tuple(sorted((k, v) for k, v in cfg["updater"].items()
                      if k != "kind"))
    spec = model.param_spec(cfg)
    params = make_weights(spec, seed)
    states = make_states(model, cfg, seed)
    step, adam = _reference_step(cell, precision, fault), _adam_leaf(hp)
    losses, first, m, v = [], None, {}, {}
    routing, size_led = [], {}
    for t, (x, y) in enumerate(batches[:n], 1):
        forced = {k: jnp.asarray(a) for k, a in
                  (selected[t - 1] if selected else {}).items()}
        (loss, seen), grads = step(params, states, jnp.asarray(x),
                                   jnp.asarray(y), forced)
        losses.append(float(loss))
        routing.append(route_report(jax.device_get(seen)))
        host = jax.device_get(grads)
        del grads
        first = host if first is None else first
        for k in params:
            # what the host holds of a leaf goes as the leaf is updated
            g = jnp.asarray(host[k] if first is host else host.pop(k))
            if t == 1:
                size_led[k] = step_size_share(g, hp)
            params[k], mk, vk = adam(
                params[k], g,
                jnp.zeros_like(g) if t == 1 else jnp.asarray(m.pop(k)),
                jnp.zeros_like(g) if t == 1 else jnp.asarray(v.pop(k)),
                jnp.float32(t))
            if t < n:
                m[k], v[k] = jax.device_get((mk, vk))
    return {"losses": losses, "first_grads": first, "routing": routing,
            "size_led": size_led,
            "change_norms": change_norms(params, spec, seed)}


def route_report(seen):
    """How the experts a reference step followed sit in its own selection
    scores: ``{layer: {"flips": followed experts that are not among the
    reference's own top-k, "pairs", "worst": how far the worst of them
    lies below the line between the reference's k-th and next score}}``
    (0 where it chose itself)."""
    out = {}
    for layer, (scores, chosen) in seen.items():
        scores, chosen = np.asarray(scores), np.asarray(chosen)
        k = chosen.shape[1]
        ranked = -np.sort(-scores, axis=-1)
        line = 0.5 * (ranked[:, k - 1] + ranked[:, k])
        margin = np.take_along_axis(scores, chosen, axis=-1) - line[:, None]
        out[layer] = {"flips": float((margin < 0).sum()),
                      "pairs": float(margin.size),
                      "worst": float(max(-margin.min(), 0.0))}
    return out


def numbers(program, reference):
    """``compare.numbers``, the change norms without the leaves whose
    first step follows the size of a gradient at Adam's eps
    (``SIZE_LED_SHARE``; how many went is shown as ``change_left_out``),
    the routed experts' widest norm gap where the model names them, and,
    where the program kept its expert choice, the two numbers that judge
    it, over every expert layer of every step."""
    out = {k for k, share in reference["size_led"].items()
           if not share < SIZE_LED_SHARE}
    firm = lambda norms: {k: v for k, v in norms.items()   # noqa: E731
                          if k not in out}
    nums = compare.numbers(
        {**program, "change_norms": firm(program["change_norms"])},
        {**reference, "change_norms": firm(reference["change_norms"])})
    nums["change_left_out"] = float(len(out))
    routed = program.get("routed_leaves")
    if routed:
        nums["grad_routed_gap"], nums["grad_routed_leaf"], _ = \
            compare.worst_and_middle(compare.leaf_gaps(
                *({k: compare.norm(side["first_grads"][k]) for k in routed}
                  for side in (program, reference))))
    if program.get("selected") and any(program["selected"]):
        reports = [r for step in reference["routing"] for r in step.values()]
        nums["route_flip_share"] = sum(r["flips"] for r in reports) \
            / max(sum(r["pairs"] for r in reports), 1.0)
        nums["route_worst_margin"] = max(r["worst"] for r in reports)
    return nums


def check(cell, result, seed):
    """(correct, checks): set-up's first steps against the plain
    reference, each number beside its limit from the cell's file."""
    reference = reference_numbers(cell, result["batches"], seed,
                                  selected=result["checked"].get("selected"))
    correct, checks = compare.judge(
        numbers(result["checked"], reference), cell["limits"])
    checks["final_loss"] = result["final_loss"]
    return correct, checks

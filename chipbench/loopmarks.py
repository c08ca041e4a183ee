"""Device time by the marks a looped model's step program carries.

The step-program map of a tree that has ``LoopVertex``
(``deeplearning4j_tpu.profiler.stepprogram``) gives every instruction,
after phase, layer, kernel and ``mixed``, the pass of the loop it runs in
(``loop_pass``, from 1; ``None`` outside a loop, as in every op of the
sparse and hybrid decoders), the part it belongs to (``attn_core``:
scores, softmax and weighted sum; ``head_loss``: a head and its loss) and
whether it is forward work a rematerialised stretch runs again in the
backward pass (``remat``). The readers of ``loop_stack_device_ms``,
``head_loss_device_ms``, ``remat_device_ms``, ``attn_device_ms`` and
``attn_roofline`` sum device-op time by those marks. A map without them
(a program from before the loop construct) gives ``None``: no reading,
never a guess.
"""

import bisect
import statistics

from chipbench import programspans as ps

LOOP_PASS, PART, REMAT = 4, 5, 6    # positions in a map entry


def in_stack(entry) -> bool:
    return entry[LOOP_PASS] is not None and entry[PART] != "head_loss"


def in_heads(entry) -> bool:
    return entry[PART] == "head_loss"


def in_attention(entry) -> bool:
    return entry[PART] == "attn_core"


def is_remat(entry) -> bool:
    return bool(entry[REMAT])


def marked_ms(ctx, want):
    """Median over the traced steps of the busiest device's op time whose
    map entry ``want(entry)`` accepts, in ms; ``None`` without a map that
    carries the marks."""
    red = ctx.reduced
    smap = ps.step_map(red, ps.of(ctx).maps)
    if smap is None or not any(len(e) > REMAT for e in smap.values()):
        return None
    dev = red.busiest()
    starts = [m[0] for m in dev.modules]
    per_step = [0.0] * len(dev.modules)
    for s, e, name, _cls, _b in dev.ops:
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or e > dev.modules[k][1]:
            continue
        entry = smap.get(ps.head(name))
        if entry is not None and len(entry) > REMAT and want(entry):
            per_step[k] += e - s
    return 1e3 * statistics.median(per_step)

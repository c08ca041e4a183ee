"""What the readers of the sparse decoder's per-layer metrics share: the
parts the step-program map gives an instruction (``mhc``: a hyper-
connection's read or write; ``moe``: a sparse-expert layer's routed path,
``moe_experts`` inside it: the grouped products over the experts held;
``attn_core``), the multi-token-prediction module's layers by their node
names, and the gauges the program sets once after ``fit`` from its layer
states. A program without the marks or the gauges gives ``None``."""

import re

from chipbench import loopmarks as lm

LAYER = 1           # position in a map entry
_MTP = re.compile(r"dl4j_L\d+_mtp\d*_")


def in_mhc(entry) -> bool:
    return entry[lm.PART] == "mhc"


def in_moe(entry) -> bool:
    return entry[lm.PART] in ("moe", "moe_experts")


def in_experts(entry) -> bool:
    return entry[lm.PART] == "moe_experts"


def in_mtp(entry) -> bool:
    """An op of the multi-token-prediction module's own layers; the
    module's share of the shared head is ``dl4j_head_loss``'s."""
    return bool(entry[LAYER]) and _MTP.match(entry[LAYER]) is not None \
        and entry[lm.PART] != "head_loss"


def ms_or_none(ctx, want):
    """``loopmarks.marked_ms``, with "no such op ran" as no reading."""
    return lm.marked_ms(ctx, want) or None


def gauge(name):
    """``{label values: value}`` of a labelled program gauge, or ``None``
    where the program has none of that name or never set it."""
    try:
        from deeplearning4j_tpu.profiler import get_registry
    except ImportError:
        return None
    family = get_registry().get(name)
    if family is None:
        return None
    return {labels: float(child.value)
            for labels, child in family.children().items()} or None


def held_pairs(ctx):
    """``{layer: routed pairs that met a held expert at the last step}``."""
    if not ctx.result.get("traced"):
        return None
    pairs = gauge("dl4j_moe_held_pairs")
    return None if pairs is None else {k[0]: v for k, v in pairs.items()}

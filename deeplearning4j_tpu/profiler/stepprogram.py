"""The step program's own map: which layer and phase each device op is.

The device trace names an operation by its HLO instruction
(``%fusion.1827``); nothing in the trace says which of the model's layers
that is, nor whether it ran in the forward pass, the backward pass or the
updater. The compiled program does: every instruction carries the
``jax.named_scope`` stack it was traced under in ``metadata={op_name=…}``
— ``jvp(dl4j_L12_conv)`` in the forward pass, ``transpose(jvp(dl4j_L12_
conv))`` in the backward pass, ``dl4j_updater`` in the update. The scopes
are emitted by both network forwards (``dl4j_L<i>_<name>``, see
:func:`profiler.devicetime.scope_name`) and by the step builders
(:data:`UPDATER_SCOPE`, :data:`LOSS_SCOPE`, :data:`AUGMENT_SCOPE`); the
Pallas kernels carry a ``name=`` (``dl4j_layer_norm``, …).

:func:`parse` turns a compiled module's text into ``{instruction name:
Entry(phase, layer, kernel, mixed)}`` — a pure function of the text,
jax-free. A reader joins it to a trace by the instruction's name. An
entry also says which pass of a ``LoopVertex`` the instruction runs in
(:func:`pass_scope`; the loop is unrolled, so no instruction serves two
passes), whether it belongs to the attention core or to the heads and
loss of a looped model (:data:`PARTS`), and whether it is forward work a
rematerialised stretch runs again in the backward pass.

The map of a running fit is made outside every timed stretch. While
instrumentation is active the fit loops :func:`note` each step function
once (the jit and the abstract signature of its arguments, no arrays);
:func:`flush` lowers that signature again — the trace, the lowering and
the executable the fit made are all found in JAX's in-memory caches, a
fraction of a second for ResNet-50 — and keeps the map under the
program's module name (``jit_step``, as the trace's ``XLA Modules`` line
calls it). ``set_profiling_mode`` leaving a non-OFF
mode flushes; :func:`maps` flushes on demand.
"""

from __future__ import annotations

import re
import threading
import time
import warnings
import weakref
from typing import Dict, NamedTuple, Optional

#: scopes the step builders put around what no layer owns
UPDATER_SCOPE = "dl4j_updater"
LOSS_SCOPE = "dl4j_loss"
AUGMENT_SCOPE = "dl4j_augment"

#: scopes inside a layer's own: the attention core (scores, softmax,
#: weighted sum) and the heads, gates and loss of a looped model's passes
ATTN_CORE_SCOPE = "dl4j_attn_core"
HEAD_LOSS_SCOPE = "dl4j_head_loss"
#: the hyper-connection read and write around a sub-block (stream norm,
#: maps, Sinkhorn, mixing); a sparse-expert layer's routed path (router,
#: top-k, dispatch, combine) and, inside it, the grouped products over the
#: experts held
MHC_SCOPE = "dl4j_mhc"
MOE_SCOPE = "dl4j_moe"
MOE_EXPERTS_SCOPE = "dl4j_moe_experts"
#: a gated short-convolution mixer: projections, gates and the taps
SHORTCONV_SCOPE = "dl4j_shortconv"
PARTS = {ATTN_CORE_SCOPE: "attn_core", HEAD_LOSS_SCOPE: "head_loss",
         MHC_SCOPE: "mhc", MOE_SCOPE: "moe",
         MOE_EXPERTS_SCOPE: "moe_experts", SHORTCONV_SCOPE: "shortconv"}
#: what JAX names the forward ops a ``jax.checkpoint`` runs again in the
#: backward pass
REMAT_MARK = "rematted_computation"

PHASES = ("forward", "backward", "updater")


def pass_scope(t: int) -> str:
    """The scope every op of pass ``t`` (from 1) of a LoopVertex runs
    under, its head and loss too: the loop is unrolled, so each pass has
    instructions of its own and the map can tell them apart."""
    return f"dl4j_ut{int(t)}"


class Entry(NamedTuple):
    """One instruction of the step program."""
    phase: str                  # forward | backward | updater | other
    layer: Optional[str]        # dl4j_L<i>_<name>, dl4j_loss, … or None
    kernel: Optional[str]       # the Pallas kernel's name= of a custom-call
    mixed: bool                 # a fusion whose instructions disagree on
    #                             the phase (weight gradient + Adam, …)
    loop_pass: Optional[int] = None     # pass of a LoopVertex, from 1
    part: Optional[str] = None  # attn_core | head_loss | mhc | … (PARTS)
    remat: bool = False         # forward work run again in the backward
    #                             pass (a rematerialised stretch)


_OTHER = Entry("other", None, None, False)

_SCOPE = re.compile(
    r"dl4j_(?:L\d+_[A-Za-z0-9_.\-]+|updater|loss|augment)")
_KERNEL = re.compile(r"(dl4j_[A-Za-z0-9_]+)/pallas_call")
_PASS = re.compile(r"dl4j_ut(\d+)")
# the longest name first (``dl4j_moe_experts`` before ``dl4j_moe``)
_PART = re.compile("|".join(sorted(PARTS, key=len, reverse=True)))
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)


def classify(op_name: str):
    """``(phase, layer)`` of one ``op_name``: ``dl4j_updater`` anywhere is
    the updater; ``transpose(`` is the backward pass; ``jvp(`` or one of
    this package's scopes without it is the forward pass. Where XLA
    merged instructions it joins their names with ``;``: the first
    speaks."""
    name = op_name.partition(";")[0]
    scope = _SCOPE.search(name)
    layer = scope.group(0) if scope else None
    if layer == UPDATER_SCOPE or UPDATER_SCOPE in name:
        return "updater", UPDATER_SCOPE
    if "transpose(" in name:
        return "backward", layer
    if "jvp(" in name or layer is not None:
        return "forward", layer
    return "other", None


def marks(op_name: str):
    """``(loop_pass, part, remat)`` of one ``op_name``: the pass of a
    LoopVertex it runs in, whether it is the attention core or a head
    with its loss, and whether it is rematerialised forward work."""
    name = op_name.partition(";")[0]
    ut = _PASS.search(name)
    part = _PART.findall(name)      # scopes nest: the innermost speaks
    return (int(ut.group(1)) if ut else None,
            PARTS[part[-1]] if part else None, REMAT_MARK in name)


def module_name(hlo_text: str) -> Optional[str]:
    """``jit_step`` from ``HloModule jit_step, is_scheduled=true, …``."""
    m = _MODULE.search(hlo_text)
    return m.group(1) if m else None


def _split(hlo_text: str):
    """``({computation: [(name, opcode, rest of the line, is root)]},
    entry computation's name)``."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
                if line.startswith("ENTRY"):
                    entry = m.group(1)
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        rest = line[m.end() - 1:]
        op = _OPCODE.search(rest)
        cur.append((m.group(1), op.group(1) if op else "", rest,
                    line.lstrip().startswith("ROOT ")))
    return comps, entry


def _own(rest: str):
    """(phase, layer) from an instruction's own metadata."""
    m = _OP_NAME.search(rest)
    return classify(m.group(1)) if m else ("other", None)


def _own_marks(rest: str):
    m = _OP_NAME.search(rest)
    return marks(m.group(1)) if m else (None, None, False)


#: instructions that do a fusion's heavy work; the elementwise rest rides
#: along with whatever they read and write
_LEADS = ("convolution", "dot")
_REDUCTIONS = ("reduce", "reduce-window", "select-and-scatter", "scatter")


def _fusion(body, rest: str) -> Entry:
    """What one fusion is, from its called computation's instructions.

    XLA fuses across phases freely: a backward fusion recomputes the
    forward activation it needs (cheap elementwise producers are
    duplicated into their consumers), and an Adam fusion swallows the
    last ``convert`` of its gradient. Such a fusion can only run once its
    latest input exists, so it belongs to the latest phase it holds
    (forward < backward < updater). What carries the weight is its
    convolution or matmul, else its reduction: where that is of an
    earlier phase than the latest one — a weight-gradient convolution
    fused with Adam's update — two phases' work shares one op, nothing
    can split its time, and it is ``mixed``."""
    lead = reduction = latest = None
    for _name, op, inner, _root in body:
        phase, layer = _own(inner)
        if phase not in PHASES:
            continue
        mine = (phase, layer) + _own_marks(inner)
        if op in _LEADS and lead is None:
            lead = mine
        elif op in _REDUCTIONS and reduction is None:
            reduction = mine
        if latest is None or PHASES.index(phase) > PHASES.index(latest[0]) \
                or (phase == latest[0] and latest[1] is None):
            latest = mine
    if latest is None:
        phase, layer = _own(rest)       # the fusion's own name, if any
        return Entry(phase, layer, None, False, *_own_marks(rest))
    # the pass, part and remat mark are those of the instruction that
    # gives the fusion its phase and layer: its matmul, else its
    # reduction, else its latest
    phase, layer, loop_pass, part, remat = lead or reduction or latest
    return Entry(phase, layer, None, phase != latest[0], loop_pass, part,
                 remat)


#: what the compiler adds to move data for another instruction and gives
#: no name of its own: asynchronous copies and slices into on-chip memory,
#: layout copies, bitcasts
_MOVES = ("copy-start", "copy-done", "slice-start", "slice-done", "copy",
          "bitcast", "reshape", "transpose")
_OPERAND = re.compile(r"%([\w.\-]+)")


def _first_users(instructions) -> Dict[str, str]:
    """``{instruction: the first instruction that reads it}``."""
    first_user: Dict[str, str] = {}
    for name, _op, rest, _root in instructions:
        for operand in _OPERAND.findall(rest.partition(", metadata=")[0]):
            first_user.setdefault(operand, name)
    return first_user


def _adopt_moves(instructions, out: Dict[str, Entry]) -> None:
    """An unnamed data movement works for the instruction that reads what
    it moved: it takes that instruction's phase, layer and marks (a
    ``-start`` through its ``-done``). Thousands of them a step prefetch
    operands for a looped stack's fusions; without this they are
    ``other`` and no layer's time."""
    first_user = _first_users(instructions)
    for name, op, _rest, _root in instructions:
        if op not in _MOVES or out.get(name) is not _OTHER:
            continue
        user, hops = first_user.get(name), 0
        while user is not None and out.get(user) is _OTHER and hops < 4:
            user, hops = first_user.get(user), hops + 1
        found = out.get(user)
        if found is not None and found is not _OTHER:
            out[name] = found._replace(kernel=None, mixed=False)


#: kernels the compiler writes in the place of an instruction it rewrote,
#: by the name it gives them, and the part they are: a grouped matrix
#: product (``jax.lax.ragged_dot``) becomes ``ragged-dot-<mode>.<n>``
#: custom-calls whose metadata keeps nothing of the scopes it was traced
#: under
_COMPILER_KERNELS = {"ragged-dot": "moe_experts"}


def _adopt_kernels(instructions, out: Dict[str, Entry]) -> None:
    """A kernel of the compiler's own (:data:`_COMPILER_KERNELS`) carries
    no scope: it takes the layer and marks of one of its operands'
    producers or of its first reader (the rows it multiplies were
    gathered in its layer) and the part its name stands for. Like a
    fusion it can only run once its latest input exists: of those it
    takes the latest phase, and rematerialised only if all are (a weight
    gradient reads rows the backward pass made again AND a cotangent)."""
    first_user = _first_users(instructions)
    for name, op, rest, _root in instructions:
        part = next((p for k, p in _COMPILER_KERNELS.items()
                     if name.startswith(k)), None)
        if op != "custom-call" or part is None:
            continue
        operands = _OPERAND.findall(
            rest.partition(", custom_call_target=")[0])
        near = [out[o] for o in operands + [first_user.get(name)]
                if o in out and out[o] is not _OTHER
                and out[o].phase in PHASES[:2]]
        if near:
            found = max(near, key=lambda e: (PHASES.index(e.phase),
                                             not e.remat))
            out[name] = found._replace(kernel=None, mixed=False, part=part)
        else:
            out[name] = Entry("other", None, None, False, None, part, False)


def parse(hlo_text: str) -> Dict[str, Entry]:
    """``{instruction name: Entry}`` for every instruction the device
    runs as an op of its own: those of the entry computation and of the
    computations it reaches through ``while``, ``call``, ``conditional``
    and asynchronous wrappers (a megastep's scan body). A fusion's called
    computation is read for its phase, not listed. The compiler's own
    unnamed data movements adopt their reader's entry
    (:func:`_adopt_moves`)."""
    comps, entry = _split(hlo_text)
    out: Dict[str, Entry] = {}
    seen, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, op, rest, _root in comps[comp]:
            called = dict(_CALLED.findall(rest))
            if op == "fusion":
                out[name] = _fusion(comps.get(called.get("calls"), ()),
                                    rest)
                continue
            phase, layer = _own(rest)
            kernel = None
            if op == "custom-call":
                k = _KERNEL.search(rest)
                kernel = k.group(1) if k else None
            out[name] = Entry(phase, layer, kernel, False,
                              *_own_marks(rest)) \
                if (phase != "other" or kernel) else _OTHER
            todo.extend(c for key, c in called.items()
                        if key != "to_apply" or op == "call")
            branches = _BRANCHES.search(rest)
            if branches:
                todo.extend(b.strip().lstrip("%")
                            for b in branches.group(1).split(","))
        _adopt_moves(comps[comp], out)
        _adopt_kernels(comps[comp], out)
    return out


# ------------------------------------------------- the running fit's maps
_LOCK = threading.Lock()
_NOTED = weakref.WeakSet()      # jits noted already: once per function
_PENDING = []                   # (jit, abstract arguments) not yet mapped
_MAPS: Dict[str, Dict[str, Entry]] = {}
#: seconds the last flush that had something to build took
last_flush_s = 0.0


def _abstract(a):
    """The argument as jit saw it: shape, dtype, and the sharding only of
    a committed array (a plan's ``device_put``). An uncommitted one lowers
    with its sharding left open, and naming it would lower — and compile —
    another program than the one that ran."""
    import jax
    committed = getattr(a, "committed", False)
    return jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=a.sharding if committed else None,
        weak_type=bool(getattr(a, "weak_type", False)))


def note(jit, args) -> None:
    """Remember what it takes to lower ``jit`` for ``args`` again: the
    function and the arguments' shapes, dtypes and shardings. Called by
    the fit loops at a step's dispatch while instrumentation is active;
    the first signature a function is dispatched with is the one
    mapped."""
    if jit in _NOTED:
        return
    import jax
    spec = jax.tree_util.tree_map(_abstract, tuple(args))
    with _LOCK:
        _NOTED.add(jit)
        _PENDING.append((jit, spec))


def _compiled_text(jit, spec) -> str:
    """The compiled step's text with this tree's scopes in it. JAX leaves
    metadata out of its compilation-cache key, so an executable cached by
    a tree without the scopes is found again and says what that tree
    said: such a program is compiled once more under a key that holds the
    metadata (the instructions and their names are the same, the key
    apart). The explicit option only steps past the executable the
    lowering keeps; it is the compiler's default."""
    import jax
    lowered = jit.lower(*spec)
    text = lowered.compile().as_text()
    if UPDATER_SCOPE in text:
        return text
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return lowered.compile(compiler_options={
            "xla_embed_ir_in_executable": False}).as_text()
    finally:
        jax.config.update(key, was)


def flush() -> None:
    """Build the map of every noted step function and let go of it. A
    program that cannot be lowered again, or whose text names no
    :data:`UPDATER_SCOPE` even when compiled afresh, gets no map and a
    warning (every reader then reports nothing, never a wrong split): a
    map is an instrument, never a reason for a fit to fail."""
    global last_flush_s
    with _LOCK:
        pending, _PENDING[:] = list(_PENDING), []
    if not pending:
        return
    t0 = time.perf_counter()
    for jit, spec in pending:
        try:
            text = _compiled_text(jit, spec)
            name = module_name(text)
            if name is None:
                continue
            if UPDATER_SCOPE not in text:
                warnings.warn(
                    f"step-program map of {name} not kept: the compiled "
                    f"program names no {UPDATER_SCOPE} scope",
                    stacklevel=2)
                _MAPS.pop(name, None)
                continue
            _MAPS[name] = parse(text)
        except Exception as e:  # noqa: BLE001 — whatever lowering raises
            warnings.warn(f"step-program map not built: "
                          f"{type(e).__name__}: {e}", stacklevel=2)
    last_flush_s = time.perf_counter() - t0


def maps() -> Dict[str, Dict[str, Entry]]:
    """``{module name: {instruction name: Entry}}`` of every step
    function noted so far, pending ones built now."""
    flush()
    return dict(_MAPS)


def clear() -> None:
    """Forget every map and every pending note (tests)."""
    with _LOCK:
        _PENDING[:] = []
        _MAPS.clear()
        _NOTED.clear()

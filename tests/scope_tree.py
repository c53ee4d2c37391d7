"""The named-scope tree of a lowered training step, read from its HLO:
which phase of ``repro.obs.annotations.PHASES`` each instruction sits
under.  Shared by ``test_scopes.py`` and ``scopes_spmd_script.py``."""
import re

from repro.obs.annotations import LAYER_PARTS, PHASES

_PHASE = re.compile("|".join([r"layer\d+"] + [re.escape(k) for k in PHASES
                                               if k != "layer{i}"]))
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def step_op_names(lowered) -> list:
    """``op_name`` of each instruction of the step's body that has one:
    the entry computation, or under ``shard_map`` the body of its manual
    computation (the entry then only calls it).  Parameters are left out
    (theirs is the argument's path), and so are the computations the body
    calls, whose names are relative to the call."""
    text = lowered.as_text(dialect="hlo", debug_info=True)
    m = re.search(r"\n(xla\.sdy\.manual_computation_body\S*) \{", text)
    body = text[m.start():] if m else text[text.index("\nENTRY"):]
    body = body[:body.index("\n}")]
    names = []
    for line in body.splitlines()[2:]:
        m = _OP_NAME.search(line)
        if m and " parameter(" not in line:
            names.append(m.group(1))
    return names


def components(op_name: str) -> list:
    """Scope names of a path, transformation wrappers stripped:
    ``jit(step)/transpose(jvp(layer1))/aggregate/mul`` holds ``layer1``
    and ``aggregate``.  An instruction that XLA merged from several ops
    carries their paths joined by ``;``: all of them count."""
    return [c for c in re.split(r"[/();]", op_name) if c]


def step_phases(lowered) -> dict:
    """``{phase: number of instructions}`` of one lowered step.  Raises
    ``AssertionError`` naming an instruction that sits under no phase or
    under several, or a layer part outside a layer."""
    count = {}
    for name in step_op_names(lowered):
        comps = components(name)
        phases = {c for c in comps if _PHASE.fullmatch(c)}
        assert len(phases) == 1, f"{name!r} sits under phases {phases}"
        phase, = phases
        if any(c in LAYER_PARTS for c in comps):
            assert phase.startswith("layer"), name
        count[phase] = count.get(phase, 0) + 1
    return count


def layer_parts(lowered) -> set:
    """The layer parts (``aggregate``, ``transform``) the step opens."""
    return {c for name in step_op_names(lowered)
            for c in components(name) if c in LAYER_PARTS}

"""Reading the collectives out of a compiled program's HLO text."""

import re

_COLLECTIVE = re.compile(
    r"= \(?(\w+)\[([\d,]*)\][^=]*? "
    r"(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute)"
    r"(?:-start)?\(")


def collectives(hlo: str) -> list[tuple[str, str, tuple[int, ...]]]:
    """``(op, element type, result dims)`` of every collective in the
    text; of a tuple-shaped result (an async ``-start``, a combined
    all-reduce) the first element's."""
    out = []
    for line in hlo.splitlines():
        m = _COLLECTIVE.search(line)
        if m:
            out.append((m.group(3), m.group(1), tuple(
                int(d) for d in m.group(2).split(",") if d)))
    return out


def squeezed(dims) -> tuple[int, ...]:
    return tuple(sorted(d for d in dims if d != 1))


def whole_batch_collectives(hlo: str, batch: int, seq: int,
                            weights=()) -> list[str]:
    """Collectives whose result carries every row of the GLOBAL batch:
    ``[batch, seq, ...]`` or ``[batch * seq (or more), ...]``.  Ids and
    per-token scalars (narrower than 16 in the last dimension) are not
    activations, and neither is a result with the dimensions of one of
    ``weights`` (``squeezed`` shapes: at the real widths ``mlp_dim``
    happens to equal ``batch * seq``)."""
    found = []
    for op, dtype, dims in collectives(hlo):
        rows = (len(dims) >= 3 and dims[:2] == (batch, seq)) or (
            len(dims) >= 2 and dims[0] >= batch * seq)
        if rows and dims[-1] >= 16 and squeezed(dims) not in weights:
            found.append(f"{op} {dtype}{list(dims)}")
    return found


_COMPUTATION = re.compile(r"^%?([\w.\-]+) \(.*\) -> .* \{$")
_FUSION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .* fusion\(.*"
                     r"calls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def fusion_bodies(hlo: str) -> dict[str, list[str]]:
    """``{fusion instruction: the lines of the computation it calls}``
    of a compiled program's text; the instruction's name is the op's
    name in a profile."""
    bodies, name = {}, None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            bodies[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            bodies[name].append(line)
    return {m.group(1): bodies.get(m.group(2), [])
            for m in map(_FUSION.match, hlo.splitlines()) if m}


def matmul_fusions_with_reduce(hlo: str, under=()) -> list[str]:
    """The fusion instructions of a compiled TPU program whose fused
    computation holds both a ``convolution`` (what a matmul compiles to)
    and a ``reduce``: a matmul with a reduction in its epilogue or
    prologue.  With ``under``, only those in which a convolution or a
    reduce has one of these strings in its ``op_name``."""
    found = []
    for name, body in fusion_bodies(hlo).items():
        ops = {op: [_OP_NAME.search(b) for b in body if f" {op}(" in b]
               for op in ("convolution", "reduce")}
        if not (ops["convolution"] and ops["reduce"]):
            continue
        names = [n.group(1) for ns in ops.values() for n in ns if n]
        if not under or any(u in n for n in names for u in under):
            found.append(name)
    return found

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

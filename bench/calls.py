"""The model-step executions of the traced stages, with the operations
and bytes each one needs (``flops.py``)."""
from __future__ import annotations

import dataclasses

import flops
import trace_reduce

PREFILL = "prefill_fn"      # names of the program's jitted steps
DECODE = "decode_fn"


@dataclasses.dataclass(frozen=True)
class Call:
    kind: str             # "prefill" or "decode"
    chip: int
    start: float          # trace clock, ns
    end: float
    ops: float
    bytes: float

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


def of_stage(view, rec, lo: float, hi: float) -> list[Call]:
    """The prefill and decode executions of one stage, in ``[lo, hi]``.

    A chip runs its shards' steps in the order they were sent: each
    shard's prefill, then its decode steps at positions ``prompt_len``,
    ``prompt_len + 1``, ...; the shards a stage puts on one chip hold
    equally many queries.
    """
    m = view.models[rec.model]
    p, g = view.traffic.prompt_len, view.traffic.gen_len
    batch = dict(zip(rec.chips, rec.shard_sizes))
    out, step = [], {}
    for chip, name, s, e in trace_reduce.executions(view.trace, lo, hi, ""):
        if PREFILL in name:
            step[chip] = 0
            ops, nbytes = flops.prefill(m, batch[chip], p)
            out.append(Call("prefill", chip, s, e, ops, nbytes))
        elif DECODE in name:
            ops, nbytes = flops.decode(m, batch[chip], p + step[chip], p + g)
            step[chip] += 1
            out.append(Call("decode", chip, s, e, ops, nbytes))
    return out


def traced(view) -> list[Call]:
    return [c for rec, lo, hi in view.stages
            for c in of_stage(view, rec, lo, hi)]


def roofline(view, kind: str):
    """Least time the chip could take for the ``kind`` calls over the
    time they took, in percent; ``None`` where none was traced."""
    cs = [c for c in traced(view) if c.kind == kind]
    if not cs:
        return None
    pk = view.peaks
    least = sum(max(c.ops / pk["bf16_flops"],
                    c.bytes / pk["hbm_bytes_per_s"]) for c in cs)
    return 100.0 * least / sum(c.seconds for c in cs)


def bound(view, kind: str) -> str:
    """Which bound holds the ``kind`` calls: compute or memory."""
    cs = [c for c in traced(view) if c.kind == kind]
    pk = view.peaks
    t_ops = sum(c.ops for c in cs) / pk["bf16_flops"]
    t_bytes = sum(c.bytes for c in cs) / pk["hbm_bytes_per_s"]
    return "compute" if t_ops >= t_bytes else "memory"

"""GB of weights copied between chips per workflow served in the traced
window.

Rebuilt from the traced stage records in order: a stage makes its model
resident on each of its virtual devices; virtual device ``d`` lives on
chip ``view.chips[d % chips]``, and ``view.chips[0]`` is home, where the
weights already are; a chip holds a model while one of its virtual
devices has it resident.  A switch copies the model's parameter bytes
when it makes the model resident on a chip other than home that did not
hold it.  Before its first traced stage a virtual device's residency is
unknown: the stage's ``switches`` settles it where the count leaves one
choice (none or all of the unknown shards switched); otherwise that
shard is not counted, and neither is a copy onto a chip where another
virtual device's residency is still unknown.  On one chip nothing is
copied between chips, and there is nothing to read.
"""
import math

import numpy as np

import weights


def model_bytes(m) -> int:
    """Bytes of every parameter of ``m`` in the dtype it is served in."""
    return sum(math.prod(leaf.shape) * (m.layers if leaf.per_layer else 1)
               * np.dtype(leaf.dtype).itemsize
               for leaf in weights.leaves(m))


def live_devices(rec, chip_of) -> list:
    """The virtual devices of ``rec`` that served a shard: the
    placement's devices whose chips, in order, give ``rec.chips``."""
    out, k = [], 0
    for d in rec.device_ids:
        if k < len(rec.chips) and chip_of(d) == rec.chips[k]:
            out.append(d)
            k += 1
    return out


def copied_bytes(view) -> list:
    """``(record, bytes)`` for each traced stage, in order."""
    n_chips = len(view.chips)
    n_devices = view.cell.n_devices

    def chip_of(d):
        return view.chips[d % n_chips]

    resident: dict = {}          # virtual device -> model, once known
    out = []
    for rec, _, _ in view.stages:
        dids = live_devices(rec, chip_of)
        unknown = [d for d in dids if d not in resident]
        known_switches = sum(resident[d] != rec.model for d in dids
                             if d in resident)
        unknown_switches = rec.switches - known_switches
        settled = (True if unknown_switches == len(unknown)
                   else False if unknown_switches == 0 else None)
        nbytes = 0
        for d in dids:
            if d in resident:
                switched = resident[d] != rec.model
            elif settled is None:
                resident[d] = rec.model
                continue
            else:
                switched = settled
            chip = chip_of(d)
            others = [e for e in range(n_devices)
                      if e != d and chip_of(e) == chip]
            if (switched and chip != view.chips[0]
                    and all(e in resident for e in others)
                    and all(resident[e] != rec.model for e in others)):
                nbytes += model_bytes(view.models[rec.model])
            resident[d] = rec.model
        out.append((rec, nbytes))
    return out


def read(view):
    if len(view.chips) < 2 or not view.workflows:
        return None
    done = {w.index for w in view.workflows}
    total = sum(b for rec, b in copied_bytes(view) if rec.index in done)
    return total / len(done) * 1e-9

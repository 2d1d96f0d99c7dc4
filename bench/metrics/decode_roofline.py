"""The decode steps' share of their roofline: the least time the chip
could take for their operations or their bytes (the weights and the whole
KV cache a step reads), over the device time they took."""
import calls


def read(view):
    return calls.roofline(view, "decode")

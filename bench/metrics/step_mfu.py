"""Model operations of every prefill and decode step in the traced
window, over their summed device time at the chip's bf16 peak."""
import calls


def read(view):
    cs = calls.traced(view)
    if not cs:
        return None
    return (100.0 * sum(c.ops for c in cs)
            / (sum(c.seconds for c in cs) * view.peaks["bf16_flops"]))

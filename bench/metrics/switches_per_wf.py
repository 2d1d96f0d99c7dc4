"""Residency switches (``StageResult.switches``) per workflow served in
the traced window."""


def read(view):
    if not view.workflows:
        return None
    done = {w.index for w in view.workflows}
    return (sum(rec.switches for rec, _, _ in view.stages
                if rec.index in done) / len(done))

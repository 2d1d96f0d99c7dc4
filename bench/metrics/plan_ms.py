"""Mean host time of one ``policy.plan`` call (the planner: FATE's
scoring and frontier solve), from the benchmark's wrapper."""


def read(view):
    if not view.plans:
        return None
    return sum(e - s for s, e in view.plans) / len(view.plans) * 1e-6

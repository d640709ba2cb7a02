"""Mean share of the serving slots that decode in a step, in %, over the
window's steps (the engine's ``StepStats.active``)."""


def read(run):
    steps = run.window.steps
    if not steps:
        return None
    return 100.0 * sum(s[2] for s in steps) / (len(steps) * run.spec.slots)

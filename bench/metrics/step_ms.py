"""Mean device milliseconds per execution of the jitted train step, from the
trace's program events (the step's program is named after the function the
trainer jits)."""


def read(r):
    name = r.host.get("step_module")
    runs = r.trace.module_runs(lambda n: n.startswith(name)) if name else []
    return 1e3 * sum(runs) / len(runs) if runs else None

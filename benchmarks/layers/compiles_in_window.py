"""planner/pipeline: programs JAX lowered inside the window (its own
monitoring event, one per trace-and-lower, cached executable or not).
Must be 0: warm-up ends only when the autotuner has stopped asking for
new shapes."""


def read(window):
    return window.notes["lowerings"]

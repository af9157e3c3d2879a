"""kernels: grid steps a cancelled lane ran past the step of the
winner's hit, where one object is searched by every chip of the host
as ONE program whose kernels read a flag that the winner raises over
ICI: ``pow_pipeline_lone_cancel_lag_steps``, sum over count, grown in
the window (a cancelled lane an observation; a step is 0.28 ms at 128
rows x 5 tiles).  None where no lane was cancelled: a program that has
no such launch, or no such histogram."""


def read(window):
    steps, lanes = window.counters.hist("pow_pipeline_lone_cancel_lag_steps")
    return steps / lanes if lanes else None

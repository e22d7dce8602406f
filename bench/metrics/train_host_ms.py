"""Host time of one client training step as the program times it: its
``train.step`` span (the batch's slicing, its upload and the step's
dispatch, with no wait on the device), the exact mean of the
``train_step_host_ns`` histogram (ms)."""

from program_telemetry import mean_ns


def read(run):
    ns = mean_ns(run, "train_step_host_ns")
    return None if ns is None else ns * 1e-6

"""Host time of one served read as the program times it: the
``serve.read`` span of ``FedCCL.model_for`` (tier choice and store read),
the exact mean of the ``serve_read_ns`` histogram (ms)."""

from program_telemetry import mean_ns


def read(run):
    ns = mean_ns(run, "serve_read_ns")
    return None if ns is None else ns * 1e-6

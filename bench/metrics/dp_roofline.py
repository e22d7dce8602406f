"""Share of the HBM roofline of the DP clip-and-noise kernel: the logical
bytes of each release (delta and noise read, release written, at the
configuration's dtype) over the device time of the kernel's program
(``jit_dp_clip_noise_tiled``) and the chip's HBM bandwidth (%)."""

from costs import dp_bytes, dtype_bytes, param_count, roofline_share


def read(run):
    t = run.trace.modules.get("jit_dp_clip_noise_tiled") if run.trace else None
    if not t or not t[1] or not run.peak:
        return None
    nbytes = t[1] * dp_bytes(param_count(run.model), dtype_bytes(run.model))
    return roofline_share(0.0, nbytes, t[0], run.peak)

"""Device time of the WGL kernels (summed ``XLA Modules`` durations of
their programs in the profiler trace), per thousand histories."""

#: Program names of the WGL kernels as the trace shows them: the scan
#: and Pallas kernels (``check``), the fused multi-bucket scan
#: (``fused``), the decrease-and-conquer peel (``one``) and the
#: sharded variants.
WGL_MODULES = ("jit_check", "jit_fused", "jit_one", "jit_sharded",
               "jit_kern")


def kernel_s(run):
    tr = run.trace
    if tr is None:
        return None
    s = tr.kernel_s(WGL_MODULES)
    return s if s > 0 else None


def read(run):
    s = kernel_s(run)
    if s is None or run.khist <= 0:
        return None
    return s * 1e3 / run.khist

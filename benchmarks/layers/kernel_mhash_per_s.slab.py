"""kernels: trials the single-object slab program (``pallas_search``)
computed in the window over its summed device time in the trace."""

from benchmarks.layers._kernels import kernel_mhash_per_s


def read(window):
    return kernel_mhash_per_s(window, "slab")

"""kernels: as ``kernel_mhash_per_s.slab``, where one object is searched
by every chip of the host at once: the trials all chips' launches of
the single-object slab program (``pallas_search``) computed in the
window, those abandoned unread after another chip's hit too, over its
device time in the trace averaged over the chips
(``tracereduce.reduce_trace``): all chips together."""

from benchmarks.layers import _twin

read = _twin.of("kernel_mhash_per_s.slab")

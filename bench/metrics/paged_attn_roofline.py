"""Kernels: the paged-attention kernel's share of its roofline, in %.

The least time the chip needs for the attention the window's steps served
(``work.roofline_seconds`` over each step's real rows and contexts; padded
rows and pages past a context are not work), over the device time of the
kernel's operations (``paged_mixed_attention_pool``) in the trace."""
import work

KERNEL = "paged_mixed_attention_pool"


def read(run):
    if run.trace is None or run.peak is None:
        return None
    spent = run.trace.kernel_s(KERNEL)
    if not spent:
        return None
    need = work.roofline_seconds([s.rows for s in run.steps], run.dims,
                                 run.peak)
    return 100.0 * need / spent

"""Fused step, whole: model FLOPs of the real tokens the window's steps
processed (prompt chunks and decode tokens, plus the output projection of
each row that produced a token) over the summed step walls times the
chip's bf16 peak, in %."""
import work


def read(run):
    walls = sum(s.wall for s in run.steps)
    if run.peak is None or not walls:
        return None
    n = run.dims
    flops = sum(work.token_flops(n, q, k, 0) for s in run.steps
                for q, k in s.rows)
    flops += sum(s.logit_rows for s in run.steps) * 2 * n.d * n.V
    return 100.0 * flops / (walls * run.peak["bf16_flops_per_s"])

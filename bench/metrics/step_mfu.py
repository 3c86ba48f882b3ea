"""step_mfu (%): model FLOPs of the rounds completed in the traced window
over what the cell's chips could do in it at their bf16 peak.  Model FLOPs
per round are one full-batch GCN epoch (``harness.work``), counted from N,
nnz(Ã) and the widths; the rounds and the window are the harness's own.
The configuration computes float32 at ``highest`` precision, so the bf16
peak is a bound the program cannot reach."""
from harness import trace, work


def read(ctx):
    w = trace.window_s(ctx["trace"])
    if ctx["rounds"] <= 0 or w <= 0:
        return None
    flops = work.model_flops_per_round(ctx["n"], ctx["nnz"], ctx["dims"])
    return 100.0 * flops * ctx["rounds"] / (
        w * ctx["chips"] * ctx["peak"]["flops"])

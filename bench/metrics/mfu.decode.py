"""Whole decode step: model FLOPs of the tokens the window's decode
steps produced (linears at their ranks, attention over each live
context, the head), over the steps' summed seconds, over the bf16
peak (%)."""
import roofline
import stats


def read(rec):
    secs = sum(s["decode_s"] for s in rec["steps"] if s["live"] > 0)
    if secs <= 0:
        return None
    cfg, lins = rec["config"], rec["linears"]
    flops = stats.decode_token_flops(
        rec, lambda ctx: roofline.token_flops(cfg, lins, ctx, head=True))
    return 100.0 * flops / secs / rec["peaks"].flops_bf16

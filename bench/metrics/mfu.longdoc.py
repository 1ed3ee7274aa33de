"""Whole step: model FLOPs of every token the window processed (prompt
chunks under causal attention, decoded tokens over their contexts,
the head where logits are used), over the window's seconds times the
bf16 peak (%)."""
import roofline
import stats


def read(rec):
    cfg, lins = rec["config"], rec["linears"]
    flops = stats.decode_token_flops(
        rec, lambda ctx: roofline.token_flops(cfg, lins, ctx, head=True))
    for s in rec["steps"]:
        for start, count, prompt_len in s["chunks"]:
            flops += roofline.prefill_flops(
                cfg, lins, start, count, head=start + count == prompt_len)
    return (100.0 * flops / rec["window"]["seconds"]
            / rec["peaks"].flops_bf16)

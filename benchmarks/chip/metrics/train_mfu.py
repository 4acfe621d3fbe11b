"""``train_mfu``: model FLOPs per trained token (6 per weight multiplied
plus causal attention, nothing recomputed; ``flops.train_token``) times
the window's train tokens per second, over the chip's bf16 peak, in %."""

import flops


def read(rec):
    w = rec["window"]
    if not w["steps"]:
        return None
    tok_s = w["steps"] * rec["tokens_per_step"] / w["seconds"]
    per_tok = flops.train_token(rec["conf"]["config"], rec["seq"])
    return 100.0 * per_tok * tok_s / rec["peaks"]["bf16_flops"]

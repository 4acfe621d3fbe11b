"""``train_mfu``: model FLOPs per trained token (6 per weight multiplied
plus causal attention, nothing recomputed; ``flops.train_token``) times
the window's train tokens per second (as ``train_tok_s`` counts them),
over the bf16 peak of the cell's chips, in %."""

import flops


def read(rec):
    w = rec["window"]
    if not w["steps"]:
        return None
    tok_s = (w["steps"] + w["partial"]) * rec["tokens_per_step"] \
        / w["seconds"]
    per_tok = flops.train_token(rec["conf"]["config"], rec["seq"])
    return 100.0 * per_tok * tok_s / (rec["cell"]["chips"]
                                      * rec["peaks"]["bf16_flops"])

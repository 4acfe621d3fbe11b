"""``train_tok_s``: every token of every train step that completed inside
the window, over the window's length."""


def read(rec):
    w = rec["window"]
    return w["steps"] * rec["tokens_per_step"] / w["seconds"]

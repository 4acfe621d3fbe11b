"""``train_tok_s``: every token of every train step that completed inside
the window, and the share of the step in flight at the close that ran
inside it, over the window's length."""


def read(rec):
    w = rec["window"]
    return (w["steps"] + w["partial"]) * rec["tokens_per_step"] / w["seconds"]

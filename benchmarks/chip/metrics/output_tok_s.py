"""``output_tok_s``: every output token emitted inside the window, over
the window's length."""


def read(rec):
    return rec["window_tokens"] / rec["window"]["seconds"]

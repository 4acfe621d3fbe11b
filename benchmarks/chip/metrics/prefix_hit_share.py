"""``prefix_hit_share``: share of full prompt blocks served from the
prefix cache over the window, in % (engine counters
``prefix_hit_blocks`` / ``prompt_blocks``)."""


def read(rec):
    total = rec["window"]["prompt_blocks"]
    if not total:
        return None
    return 100.0 * rec["window"]["prefix_hit_blocks"] / total

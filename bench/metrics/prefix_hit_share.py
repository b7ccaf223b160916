"""Share of the window's prompt tokens that the prefix cache served: aliased
block tokens over aliased plus computed prompt tokens (engine counters).
The engine counts a prompt's last token with the decode tokens; the
prompts whose first token came in the window add it back."""
import series

UNIT = "%"


def read(data):
    hit = data.counters.get("prefix_hits", 0.0) * data.block_size
    t0, t1 = data.window
    firsts = sum(1 for r in data.reqs if r.times and t0 <= r.times[0] < t1)
    computed = data.counters.get("prefill_tokens", 0.0) + firsts
    if hit + computed <= 0:
        return None
    return 100.0 * hit / (hit + computed)

"""Share of the prefill calls' token slots that carry a real prompt token:
the engine's counters over the window (``serve/prefill_chunk_tokens`` over
``serve/prefill_token_slots``, max_seqs x chunk_size a call)."""
UNIT = "%"


def read(data):
    slots = data.counters.get("prefill_token_slots")
    if not slots:
        return None
    return 100.0 * data.counters.get("prefill_chunk_tokens", 0.0) / slots

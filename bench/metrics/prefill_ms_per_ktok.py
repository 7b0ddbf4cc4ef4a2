"""Device time of the chunked paged prefill per 1000 prompt tokens: the
``jit_prefill_chunk_slot_paged`` program's device time on chip 0 over the
traced requests' prompt tokens.  Layer: model prefill
(``Model.prefill_chunk_slot_paged``)."""
PROGRAM = "prefill_chunk_slot_paged"


def read(run):
    ns = run.trace.module_ns(0, lambda n: PROGRAM in n)
    tokens = sum(p for p, _ in run.served)
    if not ns or not tokens:
        return None
    return ns / 1e6 / (tokens / 1000)

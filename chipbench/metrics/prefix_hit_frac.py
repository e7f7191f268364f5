"""Share of admitted prompt tokens served from the prefix cache.

Layer: scheduler and KV manager (``core/kvcache.py`` ``PagedAllocator``,
``core/policies.py``).  Source: the window's delta of the allocator's
``prefix_shared_tokens`` counter over the prompt tokens of the requests
admitted in the window.  Moves ``ttft_p90_ms``: a shared token is a
token no prefill computes.
"""


def read(run):
    admitted = run.counters.get("prompt_tokens_admitted", 0)
    if not admitted:
        return None
    return run.counters["prefix_shared_tokens"] / admitted

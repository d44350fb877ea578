"""Highest pool.in_use() / pool.num_pages that the load generator's thread
sampled between its own calls during the window."""


def read(trace, spans, facts):
    return facts.get("pool_peak_share")

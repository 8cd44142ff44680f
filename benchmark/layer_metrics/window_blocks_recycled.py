"""Window blocks recycled a decode step: ring entries that a new block of
positions overwrote because the window had left them
(``cache.window_blocks_recycled``, the allocator's counter, the window's
share over its steps). With every session past its ring, a sixteenth of the
sessions crosses a block boundary a step: 4 of 64. ``None`` where the cache
has no window pool."""


def read(obs):
    return obs.facts.get("window_blocks_recycled")

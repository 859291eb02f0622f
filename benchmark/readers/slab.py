"""The slab attention layers of a decode step: how much of the k/v slab
their reads took.

The program counts, in ``engine.stats()`` (window deltas in
``ctx.engine_stats``), a decode step and a slab attention layer at a time:
``slab_positions_read`` (positions the attention read, summed over slots —
the kernel reads a slot's position tiles up to its position and nothing of
a stopped lane's, the einsum body every position) and
``slab_positions_held`` (slots x T_max: what the slab holds). A program
without the counters gives None."""


def read_share(ctx):
    """Positions the decode steps' slab attention read, of the positions
    the slab held, over the window."""
    st = ctx.engine_stats or {}
    read = st.get("slab_positions_read")
    held = st.get("slab_positions_held")
    if read is None or not held:
        return None
    return 100.0 * read / held

"""Bytes one engine step must move through device memory, from shapes.

This is what the algorithm needs, not what the compiler emitted
(``cost_analysis()`` counts every temporary the compiler chose to
materialise): one replica's state read once and written once, the peers'
gathered blobs, the request ring and the small per-row vectors read, the
outputs and the fresh blob written.  The engine is int32 throughout and
has no matrix product, so the step is memory-bound and its roofline is
these bytes over the chip's memory bandwidth.

The leaf counts are the engine's layout at the commit this benchmark was
added on (``ops/engine.py``: ``EngineState``, ``Blob``, ``StepOutputs``);
they are kept here, not imported, so that a change to the program cannot
move the yardstick.
"""

WORD = 4  # int32

# (leaves shaped [G], leaves shaped [G, W])
STATE_LEAVES = (12, 7)
BLOB_LEAVES = (4, 4)
OUTPUT_LEAVES = (6, 3)


def _words(leaves, G, W):
    per_row, per_lane = leaves
    return G * (per_row + per_lane * W)


def step_bytes(n_groups, window, req_lanes, n_replicas, steps_per_dispatch=1):
    """Bytes read plus bytes written by one dispatch of the
    ``packed_host`` step (one replica's state; ``steps_per_dispatch``
    substeps over a ``[N, G, K]`` request ring)."""
    G, W, K, R, N = n_groups, window, req_lanes, n_replicas, \
        steps_per_dispatch
    state = _words(STATE_LEAVES, G, W)
    blob = _words(BLOB_LEAVES, G, W)
    out = _words(OUTPUT_LEAVES, G, W)
    read = (
        N * state         # the state, once per substep
        + R * blob        # the gathered [R, NB] matrix
        + N * G * K       # the request ring
        + G               # want_coord [G] (one word a row, as bool or int)
        + R               # heard [R]
        + G               # the heat accumulator
    )
    written = (
        N * state         # the new state
        + N * out         # the packed outputs of each substep
        + blob            # the fresh blob
        + G               # the heat accumulator
    )
    return WORD * (read + written)

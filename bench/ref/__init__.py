"""The benchmark's plain references, written from the deployment's stated
rules and importing nothing of the program under test.

A mix names its reference in `reference` (`placed` where it names none).
Each reference module, `<reference>.py`, defines:

    LAYERS       the layer names it models besides placement; a mix that
                 turns on any other layer is refused before set-up;
    COUNT_KEYS   the row keys that hold counts, compared exactly;
    sweep(cfg, inputs, targets) -> (rows, plan)
                 the rows of `targets` and the region plan (a dict with
                 the (T, N) `assign`), or `(rows, None)` where the mix has
                 no region plan.

A reference may import other modules of this package, never the program.
"""

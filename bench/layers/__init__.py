"""The layers a traffic mix can turn on, one file each: `<layer>.py`.

A mix (`bench/mixes/<mix>.json`) maps layer names to their parameters in
`layers`. The harness loads `bench/layers/<name>.py` for each, which
defines:

    inputs(cfg, params, seeds) -> dict
        host arrays the layer draws during set-up (counted in `gen_s`),
        merged into the cell's inputs; `seeds` holds the run's stream
        seeds (`bench.gen.fleet.stream_seeds`), one of them under the
        layer's own name;
    program(cfg, params, inputs) -> dict
        the `SweepSpec` keywords the layer sets on the timed path, such
        as `{"traffic": TrafficConfig(...)}`.

A layer that has no file, two layers that set the same keyword, and a
layer that sets a keyword of the placed sweep itself are refused. The
mix's reference (`bench/ref/<reference>.py`) must list the layer in its
`LAYERS`, or the cell is refused before set-up.
"""

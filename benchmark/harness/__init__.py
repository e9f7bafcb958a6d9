"""The benchmark's own yardstick: cell lookup, traffic generation, window
arithmetic, trace reduction, peaks. Nothing here imports ``mxnet_tpu``."""

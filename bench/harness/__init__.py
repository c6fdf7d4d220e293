"""The serving benchmark's shared code: cell lookup, traffic generation,
set-up and drivers, work counts, chip peaks, trace reduction and the plain
reference. Everything that belongs to one configuration, traffic mix or
metric lives in its own file under ``bench/configs``, ``bench/traffic`` and
``bench/metrics``, found by the name ``BENCHMARK.json`` gives it."""

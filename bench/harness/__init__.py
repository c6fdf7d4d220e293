"""The serving benchmark's shared code: cell lookup, traffic generation,
set-up and drivers, work counts of kernels, chip peaks, trace reduction and
the parts of the plain reference every family shares. Everything that
belongs to one configuration, traffic mix, model family or metric lives in
its own file under ``bench/configs``, ``bench/traffic``,
``bench/harness/families`` and ``bench/metrics``, found by the name
``BENCHMARK.json`` or the configuration file gives it."""

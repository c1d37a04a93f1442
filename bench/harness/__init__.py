"""General machinery of the benchmark: finding items by name, the
traffic generator, host spans, the profiler-trace reduction and the
table of peaks. Nothing here belongs to one configuration, traffic mix
or metric."""

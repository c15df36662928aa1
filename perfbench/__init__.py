"""perfbench — the benchmark of pipelinedp-tpu on the chip (BENCHMARK.json).

Everything that decides a number lives here, where a later PR that claims
a gain cannot change it: traffic, data generation, the plain reference and
the comparison that decides `correct`, the reduction from traces, spans and
counters to metrics, the table of peaks and the byte count of the roofline.
From the program it takes only the system under test (`pipelinedp_tpu`),
its `rt_trace` spans and its `telemetry` counters. See README.md.
"""

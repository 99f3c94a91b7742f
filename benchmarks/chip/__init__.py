"""The chip benchmark: one cell of ``BENCHMARK.json`` per run, on a TPU.

``python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` drives the served path (``ServingPlane`` over the service
the configuration names: the TCN's ``StreamSessionService`` or the LM slot
grid) from the client side and prints one JSON result line.
Configurations, services, traffic mixes, per-layer metric readers and
correctness checks are files found by the names in ``BENCHMARK.json``.
"""

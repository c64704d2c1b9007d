"""The chip benchmark: one cell of ``BENCHMARK.json`` per run, on a TPU.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, cell, model
or per-layer metric is a file of its own, found by its name:

* ``configs/<config>.json``    deployment sizes and settings
* ``traffic/<mix>.json``       parameters of the one general generator
* ``workloads/<cell>.json``    fixed rate, seed stream and check limits
* ``metrics/<metric>.py``      a reader from the run's record to one number
* ``references/<model>.py``    a training configuration's plain reference,
                               found by its ``model``: ``init``,
                               ``reference`` and ``flops_per_triple``
* ``peaks.json``               published peaks, keyed by ``device_kind``

A new model's training cell is then a configuration, a workload, a
reference file and metric files, with no edit to the harness.

The rest is the yardstick: the traffic generator (``traffic.py``), the
out-of-process HTTP client fleet (``client.py``), the trace reduction
(``tracing.py``), the work functions (``work.py``), the plain top-k
reference and the parts of KGE training no model changes
(``reference.py``) and the comparisons that decide ``correct``
(``checks.py``).  ``serve_cell.py`` and ``train_cell.py`` drive the system
under test; nothing here is imported by the program.
"""

"""The chip benchmark: one serving cell per run, driven by data.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``  sizes as run, source, serving sizes,
  the correctness limit; ``"model"`` names the model family module;
* ``bench/models/<model>.py``      weights from the seed, the plain float32
  reference, the FLOP and byte counts of that family;
* ``bench/traffic/<traffic>.json`` the parameters the one generator reads;
* ``bench/metrics/<metric>.py``    the reader of one per-layer metric
  (``decode_step_ms.rate`` is read by ``decode_step_ms.py``).

Run one cell with ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.
"""

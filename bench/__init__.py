"""The chip benchmark of the MTGC training path.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the root of the checkout names the cells. Everything
that belongs to one configuration, one traffic mix or one per-layer metric
lives in a file of its own, which the harness finds by that name:

* ``bench/configs/<config>/``: ``config.json`` (the sizes as run, their
  source and cuts), ``model.py`` (the program's model and its FLOP count),
  ``reference.py`` (the plain float32 model and the weights) and
  ``tiny.json`` (the size at which the benchmark's CPU tests run it). A
  classifier's reference gives ``forward(cfg, params, x)``; a configuration
  whose ``task`` is ``causal_lm`` is trained on token streams, and its
  reference gives ``loss(cfg, params, {"tokens", "targets"})``;
* ``bench/traffic/<traffic>.json``: the parameters of a traffic mix, read
  by the one generator in ``bench/feed.py``;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric;
* ``bench/limits/<workload>.json``: the limits of the cell's comparison
  with the plain reference.
"""

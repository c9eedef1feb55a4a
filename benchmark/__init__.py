"""The benchmark of dlrover_tpu: cells run through the launcher path.

``BENCHMARK.json`` at the root of the checkout lists the cells; everything
that belongs to one configuration, one job (the traffic) or one per-layer
metric is a file of its own in a directory here, found by name:

- ``configs/<config>.json``        the architecture as it is run
- ``jobs/<traffic>.json``          the job: batch, optimizer, layout, kill
- ``models/<family>.py``           config file -> the program's model
- ``reference/<family>.py``        the plain float32 reference
- ``layer_metrics/<metric>.py``    one reader per per-layer metric

``run.py`` is the command. It never imports JAX: the chip belongs to the
worker (``worker.py``) that the launcher starts.
"""

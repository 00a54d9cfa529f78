"""Per-layer metric readers: ``<metric>.py``, or ``<base>.py`` for every
metric named ``<base>.<group>``.  Each defines ``read(ctx)``, which returns
the metric's value or None when the run collected nothing to read; the
harness then leaves the metric out of the result line.  ``ctx`` is the
``bench.run.TracedRun`` of one ``--trace 1`` run."""

"""Model families: ``<family>.py``, found by the ``family`` key of a
configuration file (``decoder`` where it has none).  A family module gives
what the harness needs to know of a model's block:

* ``weight_tree(key, cfg)``: the served weight tree drawn from a threefry
  key, jitted by the harness with ``cfg`` static (``bench.model.make_weights``,
  which also checks the tree against the program's served layout);
* ``score(model, weights, prompt, served, length)``: the plain float32
  reference of one request, as ``bench.reference.score`` returns it; it
  imports nothing of the program;
* ``shape_of(cfg)``, ``step_work(shape, rows)`` and
  ``step_parts(shape, rows)``: a step's FLOPs and bytes, whole and by the
  program's scopes (``bench/work.py``, ``bench/parts.py``), behind
  ``step_mfu`` and the ``<part>_roofline`` metrics;
* ``SCOPES``: the program's ``jax.named_scope`` names that a trace's
  operations are read under (``bench/scopes.py``).

A family is added as a file; the harness is not edited."""

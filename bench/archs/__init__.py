"""The architectures the benchmark serves, one module each, found by a
configuration's published ``model_type`` (``spec.arch``): a file whose
``model_type`` is ``qwen2_moe`` is served through ``qwen2_moe.py`` here and
checked against ``bench/reference/qwen2_moe.py``.  Adding an architecture
adds these two files; no code of the harness changes.

An architecture module provides, for a configuration file ``c``:

- ``program_config(c)``: the program's ``ModelConfig``;
- ``weight_layout(c)``: ``{path: (shape, init)}`` of the dense weights,
  ``init`` a normal's stddev or ``"ones"``; a path under a stack's
  ``<prefix>/`` is one layer's, which ``bench/weights.py`` stacks that
  stack's depth deep; the other paths are made once;
- ``stacks(c)``, optional: ``{prefix: depth}``, one stack per kind of
  layer, such as leading dense layers before the expert layers.  The
  prefixes are the program's own tree keys, the depths add up to
  ``num_hidden_layers``, and no prefix starts another (``spec.stacks``
  ends the run otherwise).  Without it every layer is one stack,
  ``{"blocks": num_hidden_layers}``;
- ``linear_work(c)``: ``{"linears", "head"}``, the multiply-adds one token
  row does in the converted block projections (all layers) and in the
  head.  A model with routed experts counts the experts a token is routed
  to and the shared ones, not every expert held: ``mfu`` and
  ``lut_roofline.decode`` count useful work, whatever implements it.
"""

"""The architectures the benchmark serves, one module each, found by a
configuration's published ``model_type`` (``spec.arch``): a file whose
``model_type`` is ``qwen2_moe`` is served through ``qwen2_moe.py`` here and
checked against ``bench/reference/qwen2_moe.py``.  Adding an architecture
adds these two files; no code of the harness changes.

An architecture module provides, for a configuration file ``c``:

- ``program_config(c)``: the program's ``ModelConfig``;
- ``weight_layout(c)``: ``{path: (shape, init)}`` of the dense weights,
  ``init`` a normal's stddev or ``"ones"``; paths under ``blocks/`` are one
  layer's, which ``bench/weights.py`` stacks ``num_hidden_layers`` deep;
- ``linear_work(c)``: ``{"linears", "head"}``, the multiply-adds one token
  row does in the converted block projections (all layers) and in the
  head.  A model with routed experts counts the experts a token is routed
  to and the shared ones, not every expert held: ``mfu`` and
  ``lut_roofline.decode`` count useful work, whatever implements it.
"""

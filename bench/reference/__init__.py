"""Plain references that decide ``correct``, in two kinds of module.

An architecture's module, named by a configuration's ``model_type``
(``spec.reference``), provides ``hidden(c, seed, tokens, lower=False)``,
the final normed hidden states ``(N, T, d)`` of ``tokens`` ``(N, T)``, and
``head_stats(c, seed, h, targets)``, per position the best logit, the
logit of ``targets`` and the argmax token.  Both run in float32 at
``highest`` precision, make the weights again from the seed
(``bench/weights.py``: each stack's layer ``i`` by ``weights.layer(layout,
seed, i, prefix)``, the rest by ``weights.top(layout, seed,
spec.stacks(c))``) and import nothing of the program; ``lower`` puts
every projection one precision step below the configuration's, the
control.  Each projection's arithmetic is the family's: a family's module
here (``wtab.py``, ``tl1.py``, named as in ``bench/families/``) provides
``apply_set(ws, x, conf, lower)``.  So a ``model_type`` may not take a
family's name; ``spec`` refuses one that does.
"""

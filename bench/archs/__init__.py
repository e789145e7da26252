"""Architectures: everything the harness knows about one kind of model's
layers, one module a kind, named by a configuration's ``arch`` key and
loaded by path (``bench.spec.arch``).  A new architecture is a new file.

Every architecture module gives:

  model_config(cfg)        the program's ``ModelConfig``; raises where the
                           program cannot run what the file states
  layout(cfg)              a ``bench.weights.Layout``: the layer kind at
                           each pattern position, the number of scanned
                           groups, and any leading layers outside the scan
  layer_leaves(cfg, kind)  (path, rule, shape, dtype, extra) of one layer's
                           leaves (``bench.weights.make_leaf``'s rules)
  outer_leaves(cfg)        the same for the leaves outside the layers:
                           ``embed.emb``, ``final_norm.scale`` and the
                           ``lm_head`` codes, which the reference's
                           embedding and head read
  layer(cfg, kind, p, x, *, mono, T, lower)
                           the plain reference of one layer over ``x``
                           [B, L, d] from its leaves ``p``: (output,
                           {row name: [B, L, ...]}), the rows being what
                           the program's cache holds for those positions;
                           ``lower`` names a control precision step
  cache_rows(cache, slots, P)
                           the program's rows of ``slots`` (an int32
                           array), first ``P`` positions, under the same
                           names: {row name: [n_layers, slots, P, ...]}
  kernel_calls(cfg, kernel)  (K, N, code bits) of each call of a kernel
                           (``bench/kernels.json``) in one forward
  forward(cfg, ctx)        ``bench.cost.Work`` of one forward of rows with
                           context lengths ``ctx``
  tiny(cfg, layers)        the configuration shrunk to run on a CPU in the
                           tests (``bench/tests/tiny.py``)

Generic pieces they build on: ``bench.reference`` (``rms_norm``,
``act_codes``, ``linear``, ``rope``, ``attend``), ``bench.cost`` (``Work``,
``matmul``), ``bench.weights`` (``Layout``, ``weight_format``,
``HEAD_FORMAT``).  An architecture close to another imports that one's
functions (``from bench.archs import dense_decoder``).
"""

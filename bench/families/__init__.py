"""Model families: one module per published ``model_type``, found by the
``model_type`` key of a configuration file's ``model``
(``bench/families/<model_type>.py``). Everything the benchmark knows of a
family's architecture lives in its module; the rest of ``bench/`` is the
same for every family.

Each takes the whole configuration file ``conf`` (``model``, ``reduced``,
``engine``, ``deployment``) and gives:

* ``arch_config(name, conf)``: the program's ``ArchConfig``;
* ``program_tree(key, conf, dtype)``: the weights made from ``key`` in the
  program's own parameter tree (``bench/model.py`` checks its layout);
* ``Reference(conf, seed, fp8=False)`` with ``.logits(seqs, want,
  pad_to)``, the plain reference, and ``Q_BLOCK``, the multiple ``pad_to``
  has to be;
* the counts: ``decode_flops(conf, ctx)``, ``prefill_flops(conf, spans)``,
  and each kernel's ``(flops, bytes)`` as ``<kernel>_cost(conf, ...)``.
"""

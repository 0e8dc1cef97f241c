"""One module per model family, named by the configuration's own
published ``architectures[0]`` (``spec.family``): ``<name>.py`` here.

A family module gives, for a configuration file ``model``:

- ``program_config(model)``: the program's ``ModelConfig``;
- ``tiny(model)``: the configuration at the CPU tests' size, one layer
  of each kind it has kept;
- ``canonical(seed, model, device=None)``: the seed's weights in the
  published layout, which the reference reads;
- ``for_program(seed, model, cfg, plan, device=None)``: the same
  numbers as the program's parameter tree;
- ``fingerprint_program(params, model)``: ``weights.fingerprint`` of
  the published matrices, read back out of that tree;
- ``logits(weights, model, tokens, rows, quant=False, shape=(0, 0))``:
  the plain float32 reference (``quant``: the fp8 control);
- the counts at the published widths: ``params``, ``weight_bytes``,
  ``prefill_flops(m, start, n)``, ``decode_flops(m, context)``,
  ``decode_step_bytes(m, contexts)`` and ``chunk_kernel(m, start, n)``.

It builds on what the families share: ``weights`` (``_key``, ``_draw``,
``fingerprint``), ``reference`` (``rmsnorm``, ``rope``, ``_mm``, the
blocked causal attention, the fp8 control, ``run``) and ``flops``
(``DTYPE_BYTES``, ``least_time``).  An architecture that shares another
family's code is a one-line module that re-exports it.
"""

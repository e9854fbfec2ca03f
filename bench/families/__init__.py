"""Architecture families: one module per ``model_type`` that a configuration
file declares, ``bench/families/<model_type>.py``, found by that name
(``bench/spec.py:load_family``).  A family module holds everything of the
benchmark that depends on the architecture, and defines:

* ``program_config(conf)``: the program's ``ModelConfig`` for the file;
* ``experts_held(conf, cfg)``: routed experts the program holds on a chip
  (0 for a dense model);
* ``layout(conf, held)``: the parameter tree the program serves, as
  ``bench.model.Leaf`` specs;
* ``head_dim(conf)``, ``kv_bytes_per_token(conf)``;
* ``active_matmul_params(conf)``, ``step_flops(conf, q_lens, cache_pos)`` and
  ``attention_call(conf, q_lens, cache_pos, dtype_bytes=2)``: the work of a
  step and of one layer's attention kernel, from shapes alone;
* ``layer(p, x, conf, quant)``: one decoder layer of the plain reference
  (``bench/reference.py``), float32, with the float8 control under
  ``quant``.

A new architecture arrives as a new module here, beside its configuration,
traffic and metric files.
"""

"""Architectures, each a module found by name (``chipbench/spec.py``).

A configuration file names its architecture under ``"arch"``; the module
``chipbench/arch/<arch>.py`` holds everything of a run that depends on the
model, and the harness reaches the model through it alone:

- ``dims_of(conf)``: the sizes the configuration file states, as one
  hashable value (the harness reads only its ``vocab``, for the traffic);
- ``make_weights(dims, n_adapters, seed) -> (params, lora)``: random
  weights and LoRA adapters from the seed, made on the device in the
  layout the server takes;
- ``model_config(conf, dims)``: the server's ``ModelConfig``;
- ``gaps(params, lora, dims, adapter, prompt, served, doc, s_pad, t_pad,
  control=False)``: the plain float32 reference, with the int8 control
  (``chipbench/reference.py`` says what it returns);
- ``grid_work(rows, dims, page, kind) -> {"flops", "bytes"}``: the work one
  call of the paged grid ``kind`` ("decode" or "mixed") needs, summed over
  every layer that runs it;
- ``step_flops(rows, dims)``: model FLOPs of a step's unpadded tokens.

Only the module knows the model's weights, sizes and equations.
"""
API = ("dims_of", "make_weights", "model_config", "gaps", "grid_work",
       "step_flops")

"""Model families: one file each, found by a configuration's ``family`` key
(``families/<family>.py``, loaded by ``spec.family``). A family module
provides:

* ``param_shapes(cfg)``: the nested dict of leaf shapes that
  ``reference.common.init_params`` builds the weights from, in the
  program's parameter layout;
* ``program_fields(cfg)``: the program's ``ModelConfig`` fields, by name,
  that ``program.model_config`` holds to the configuration;
* ``forward(cfg, params, tokens, quant)``: the plain reference, logits in
  float32 at ``HIGHEST`` with every matmul operand rounded by ``quant``
  (``reference.common.nll_sum`` derives the loss from it);
* ``matmul_params(cfg)``, ``train_flops_per_token(cfg, seq)`` and
  ``forward_flops(cfg, contexts)``: the model FLOPs the MFU readers charge;
* ``small(cfg, model)``: ``cfg`` at the size of the program's ``reduced()``
  ``ModelConfig`` ``model``, for the tests on the CPU;
* ``kernels``: the Pallas kernels the family's programs run, by kernel
  name, each with its cost function (``counts.kernel_roofline``);
* ``counters``: the engine's or trainer's attributes whose change over the
  window the harness records (``counts["program"]``).

A family joins as one new file, with no edit to any file that is here:
``dense.py`` holds the dense decoder that ``gpt2`` and ``llama`` name, and a
new family may import its parts.
"""

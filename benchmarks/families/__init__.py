"""One file per model family, found by the name a configuration gives as
``deployment.family`` (``harness/spec.py family``). A family supplies
what the harness must ask of a model by its name, and nothing in
``harness/``, ``readers/`` or ``tools/`` names a model:

- ``config(model, **overrides)``: the program's config object from the
  published keys of the configuration file;
- ``module()``: the program's module with the family protocol
  (``init_params`` / ``param_shardings`` / ``loss_fn``) that
  ``make_train_step(cfg, mesh, model=...)`` and ``make_eval_step`` take,
  and whose ``forward(params, tokens, cfg, mesh)`` the train parity calls;
- ``forward(params, tokens, cfg)`` and ``logits_and_loss(params, batch,
  cfg)``: the plain float32 reference at ``highest`` matmul precision,
  with the layer equations and every departure from the published
  description stated in the file's docstring;
- ``train_required_flops_per_token(model, n_layers, seq)``: what
  ``train_mfu_required`` divides by;
- ``serve_parity(params, cfg, seed, prompt_len, *, buckets, block,
  kv_impl, interpret)``: prefill then one decode step through the paged
  cache against the reference's full forward. A family without it cannot
  be served yet, and a serve cell on it is refused before the runtime
  starts.
"""

# the family of a configuration that names none: the three accepted with
# PR 23, whose files may not be edited
DEFAULT = "llama"

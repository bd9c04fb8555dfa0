"""Plain references, one module per architecture, named by a
configuration file's ``"reference"`` key.  A module gives the harness:

- ``init_tree(key, model, std, dtype)``: random weights in the layout
  the program takes for the architecture;
- ``param_count(model)`` and ``train_flops_per_token(model, seq_len)``,
  counted by the convention of :mod:`chipbench.flops`;
- ``grads``, ``clip``, ``adam`` and ``warmup_lr``: the training step the
  program's is compared with (see ``references/olmo_dense.py``);
- optionally ``scope_work(model, seq_len, itemsize)``: ``{scope:
  {"flops", "bytes"}}`` per token, the required work of each named
  scope of the program's step it counts, for roofline readers.

It imports nothing of the program.
"""

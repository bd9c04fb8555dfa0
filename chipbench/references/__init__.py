"""Plain references, one module per architecture, named by a
configuration file's ``"reference"`` key.  A module gives the harness:

- ``init_tree(key, model, std, dtype)``: random weights in the layout
  the program takes for the architecture;
- ``param_count(model)`` and ``train_flops_per_token(model, seq_len)``,
  counted by the convention of :mod:`chipbench.flops`;
- ``grads``, ``clip``, ``adam`` and ``warmup_lr``: the training step the
  program's is compared with (see ``references/olmo_dense.py``).

It imports nothing of the program.
"""

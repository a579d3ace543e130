"""One module per architecture, ``archs/<model_type>.py``, found by a
configuration file's ``model_type`` (``bench.system.arch``).

Each module defines:

- ``check(cfg)``: raise ``ValueError`` for a file that states something
  the program cannot run;
- ``model_config(cfg)``: the program's ``ModelConfig`` for the file;
- ``TINY``: the keys that ``bench.tiny`` overrides for the CPU cell;
- the counts that differ by architecture: ``layer_matrix_params(cfg)``,
  the matrix parameters one token passes through in one layer (its
  FLOPs are twice that); ``head_params(cfg)``, those of the head, which
  each row passes through once a step; ``decode_weight_bytes(cfg,
  rows)``, the least weight bytes a decode step of ``rows`` rows reads,
  the head's among them; ``kv_bytes_per_token(cfg)``, over every layer; and
  ``attention_flops(cfg, ctx)``, one query token of one layer against
  ``ctx`` keys.

``bench.counts`` composes the counts into a step's.  A module imports
only ``bench.spec`` (the element sizes, ``head_dim``, ``no_path``) and
the program.  A new architecture enters as a new module here; one that
shares another's equations imports that module's names.
"""

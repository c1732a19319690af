"""The models of the plain reference and of the FLOP count, one file per
layout kind: a configuration's ``layout.kind`` names ``models/<kind>.py``,
which :func:`bench.catalog.model` loads by its file name. A new kind of
model is a new file here; nothing else changes. A file whose name starts
with ``_`` is a helper of the kinds, never a kind itself.

Each kind's file gives these five functions:

- ``arch(layout) -> dict``: the sizes its reference needs, from the
  configuration's ``layout``, beside the ones every kind has
  (:func:`bench.reference.arch_of`: ``layers``, ``d``, ``vocab``,
  ``vocab_padded``, ``tied``, ``eps``);
- ``init(a, key)``: the whole initial parameter tree, from the scheme the
  layout states, in the tree structure and leaf order of the program's
  ``model.init``. How the layers repeat (a pattern of one layer kind or
  more) is the file's own business;
- ``hidden(a, params, tokens, pr)``: the float32 hidden states of
  ``tokens`` (b, S), from the embedding through the final norm, with every
  matmul operand read at precision ``pr`` (``"f32"`` or ``"fp8"``);
- ``matmul_params(layout) -> int``: the weights a token multiplies against,
  real sizes only (no padding; the embedding lookup is no matmul; a tied
  head counts once);
- ``forward_flops(layout, lens) -> float``: the forward FLOPs of sequences
  of the given non-pad lengths, in which each token reads its context.

The program's half of a configuration is its registry entry
(``program.registry`` with its ``overrides`` in the configuration file);
the two meet only in the parameters' shapes, which the harness compares.
"""

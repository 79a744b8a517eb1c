"""Plain references, one module to a model family, found through the
configuration file's `"reference"` key (`importlib.import_module`, as the
loops find `"program"`). The harness never tests a model's name or keys:
what differs between models lies behind these functions.

A reference module gives, with `cfg` the configuration file as loaded:

  param_shapes(cfg) -> {name: shape}, in the order the forward pass meets
      them. The names are the program's (`benchmark/programs/` maps them),
      so that one seeded set of weights (`seeded.make_weights`) serves
      both. A configuration cut to a chip's share states the counts HELD.
  num_params(cfg) -> int, of those shapes.
  forward(params, ids, cfg, precision) -> logits [rows, seq, vocabulary]
      of int32 ids [rows, seq]. `params` arrive in the type the
      configuration serves them in; the module widens them where it uses
      them, a layer at a time, so that a chip-filling set is never held
      twice.
  loss_and_grads(params, ids, labels, cfg, precision, rows_per_block)
      -> (mean next-token loss, {name: gradient}), float32 `params`, in
      blocks of rows so that one block's logits are held at a time. A
      configuration that only serves may leave it out.

What the checks read of it. A train cell follows the compiled step's
first three steps: losses, the first gradient's norm and the parameters'
change by the worst leaf. A serve cell runs `forward` once over each
sampled request's prompt and served tokens and compares TWO numbers of
the gaps by which a served token's logit lies below the reference's best
(`correct.gap_numbers`): the widest, which one wrong token fails in a
model that makes no discrete choice, and the mean over all served tokens
of the sample, each against its own limit in the cell's limits file.
Both, because of models with a discrete choice in them. Top-k of a router's scores is discontinuous: a sound program and
this reference choose another k-th expert wherever two scores lie within
rounding of each other, and at that position a whole expert's share of
the layer's output is replaced. The check is teacher-forced, so the flip
does not travel along the sequence, but the widest gap over some thousand
tokens is set by the worst flip, in a sound program as in one of lower
precision: it cannot tell them apart, and the mean can (`PERF.md`
section 4, "a model that routes"). One wrong token is then caught by
neither number, except by chance: its gap is of the size of a flip's, and
it moves the mean by its share of the sample. So a reference with such a choice in
it computes the choice ITSELF, in float32 and from its own activations,
in every `precision` (programs keep their routers in float32 too): it is
never handed the program's choice, and has no argument through which it
could be. A reference that followed the program's experts would agree
with a program that routes wrongly.

All of it is straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`: no kernel, no cache, no
batching trick. It imports nothing of the program and takes nothing the
program made. `precision` is `"float32"`, the reference itself, or
whatever the configuration's `precision.control` holds: the nearest
precision below the stated one, in which the reference stands in for a
program that a later PR was tempted to speed up. What every model shares
is in `common`: AdamW, the per-leaf norms and the rounders behind the
control precisions.
"""

"""Names of the train step's phases, as `jax.named_scope`s.

A named scope is op metadata: it reaches every device operation's
`op_name` (the `tf_op` of a profiler trace's "XLA Ops" event). The
compiled program keeps its instructions, their order and their operands;
only the numeric suffix of some instruction names may move.
`repro.dist.step` and `repro.dist.zero` put the step's phases under these
names, and a trace reader matches them:

  forward      `jax.value_and_grad` of the loss. Below it JAX names the
               forward pass `jvp(...)` and the backward pass
               `transpose(jvp(...))`; rematerialized forward work runs in
               the backward pass and carries its name.
  consensus    the gradient exchange, with children
    encode     EF add, chunking, the encoder
    exchange   the collective (all-gather, all-reduce, all-to-all)
    decode     the decoder
    mean       the mean over the workers' decoded payloads
  optimizer    global-norm clip, the optimizer update, applying it
"""
FORWARD = "forward"
CONSENSUS = "consensus"
ENCODE = "encode"
EXCHANGE = "exchange"
DECODE = "decode"
MEAN = "mean"
OPTIMIZER = "optimizer"

ALL = (FORWARD, CONSENSUS, ENCODE, EXCHANGE, DECODE, MEAN, OPTIMIZER)

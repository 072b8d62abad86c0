"""A toy second architecture, for the rehearsal only: the tiny llama block
plus a recurrent state a row (a decayed running sum over the row's tokens,
folded into the logits). It exists to show that the yardstick takes an
architecture with state beside its pages as files and entries: it is reached
only through ``configs/toy-recurrent.json``. Never a cell."""

#: values of recurrent state a row (here and not beside the weights: the
#: counts module is imported by the harness's parent, which never imports JAX)
STATE = 8

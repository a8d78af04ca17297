"""One module per architecture: all that the harness knows of a model's
configuration keys, parameter names and arithmetic.

A configuration file names its module with the key ``arch_module``, a
dotted module name under the ``bench`` package (``"archs.dense"`` is
``bench/archs/dense.py``).  ``spec.arch`` imports it and
``spec.validate`` refuses a file without the key or a module without a
function of the contract.  Each function takes the configuration dict
``c``:

* ``program_config(c)``: the program's ``ArchConfig`` at the file's
  sizes;
* ``leaf_kind(names, ndim, c)``: ``"embed"``, ``"matrix"``,
  ``"rms_scale"``, ``"ln_scale"`` or ``"ln_bias"`` for the parameter
  leaf at path ``names`` (a tuple of keys) with ``ndim`` axes; the
  draw of each kind is ``weights.init_params``'s;
* ``row_loss(params, tokens, c, precision, block, positions)``: the
  whole loss that the program optimises for one sequence ``tokens``
  [S], router losses included where a model has them, in float32 with
  the matrix products of ``reference.einsum_for(precision)``; under
  ``precision="fp8"`` the leaves ``matrices(c)`` are rounded once with
  ``reference.round_weights``; ``block`` bounds the queries and the
  loss positions computed at once; ``positions`` (or None for all)
  keeps only the first that many targets;
* ``matrices(c)``: the last keys of the leaves that the fp8 control
  rounds;
* ``total_params(c)``: every parameter the program holds;
* ``model_flops_per_token(c, seq)``: the FLOPs that the forward and
  backward passes require for one trained token at length ``seq``;
* ``attn_fwd_cost(c, seq, batch)``: ``{"flops", "bytes"}`` of one
  layer's attention forward over ``batch`` sequences;
* ``tiny(c, seq)``: a copy of ``c`` at widths a CPU test can hold, for
  sequences of ``seq`` tokens.

Everything else in the harness is shared and reads no model key, so a
new architecture is a configuration file and a module here, with its
traffic, limits and readers.
"""

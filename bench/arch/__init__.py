"""Architecture modules: one file per architecture, named by a
configuration file's ``"arch"`` key and loaded by file path
(``model.arch``), so adding an architecture is adding files.

Each module provides these functions; ``dm`` is what its own ``dims``
returns, and nothing outside the module reads more of it than the keys
named here:

``dims(conf) -> dm``
    The sizes the benchmark needs, under short names. Every module gives
    ``V`` (the vocabulary the traffic draws from) and, for the paged
    decode kernel (``flops.paged_decode_cost``), ``H``, ``KV`` and
    ``hd``; the rest is the module's own.
``model_config(conf, name)``
    The serving program's ``ModelConfig``. Raises on any key of the
    configuration that the module does not map, so a configuration is
    never served as another architecture.
``make_weights(dm, seed, device=None)``
    The weights drawn from ``seed`` (``model.weight_seed``) on the device,
    in the storage dtype, by one jitted call.
``program_params(w, cfg)``
    Those weights nested as the program's parameter tree.
``n_params(dm)``
    How many parameters ``make_weights`` draws.
``score(w, dm, tokens, first, *, targets=None, precision="float32", max_len=0)``
    The plain reference: teacher-force ``tokens`` in float32 at
    ``highest`` matmul precision and return, for each position that
    predicts a served token (``first - 1`` on), the best logit, the logit
    of ``targets`` (default: the served tokens) and the argmax.
    ``precision="fp8"`` is the control, the same computation in the
    precision below the configuration's. It imports nothing of the
    program.
``decode_token_flops(dm, context)``, ``prefill_chunk_flops(dm, start, n, logits)``
    Operations the model needs for one decode token over ``context`` keys
    and for prompt tokens ``start .. start+n-1`` (with one row of logits
    when ``logits``); multiply-adds count 2.
``paged_layers(dm)``
    How many layers run the paged decode kernel over the whole context.
"""

"""One module per model family, found by the `family` key of a
configuration file (`manifest.load_family`). The harness knows no
architecture: whatever depends on one, it asks the cell's family. A
family the benchmark has no module for is an error that names it.

A family module `benchmark/families/<family>.py` gives six answers.
`config` is the configuration file as loaded, `ctx` the run's record
(`ctx["config"]`, `ctx["itemsize"]`, `ctx["traffic"]`, the snapshots and
the reduced trace: what a reader has). The module imports nothing heavy
at its top: JAX and the program are imported inside its functions.

1. `sizes(config) -> dict`: the sizes it computes with, from the
   configuration's own published keys, whatever they are called there.
   `vocab_size` (ids the traffic may send) and `max_len` (the longest
   sequence a request may reach) are for the generator, the warm-up and
   the check's padding; the rest is the family's own.
2. `param_shapes(config) -> tree of tuples` and `is_gain(path) -> bool`
   (`path` as `jax.tree_util.keystr` spells it): `weights.make_params`
   draws every leaf N(0, 0.02) from the seed in one jitted call and
   sets the gains to 1. Any tree: stacked experts, shared experts, a
   router, no position table.
3. The program's objects: `build_engine(config, params)` (which
   constructor, which keys of the file's `serving` section; the engine
   has `decode_loop`, `generate_stream` and `close`) and
   `make_train_step(config, params) -> (step, state)` with
   `step(params, state, batch) -> (params, state, loss)` and
   `first_gradient(state)`, the first gradient as the optimizer got it,
   read from the state after one step.
4. `reference()`: the plain reference module, which imports nothing of
   the program. `logits(config, params, tokens, first, last, mode=)`
   for a padded sequence; `loss_and_grad(config, params, tokens,
   rows_per_block, mode=, rows=)`, `init_state(params)` and
   `update(config, params, state, grads)` for training. It is called
   with the configuration, never with single sizes, so a reference can
   be told what the file states beside the published counts. `mode`
   other than "f32" is the control in a lower precision.
5. The counts, from `ctx` because a count may depend on what ran and
   not only on shapes: `decode_token_flops(ctx, context)`,
   `prefill_flops(ctx, prompt_len)`, `train_flops_token(ctx, seq_len)`,
   `decode_step_bytes(ctx, contexts)`, and the work of each kernel
   call, as a list with one `{"flops", "bytes"}` for each call of one
   pass (one per layer, which need not be alike):
   `paged_decode_attention_work(ctx, contexts)`,
   `flash_fwd_work(ctx, rows, seq_len)`,
   `flash_bwd_work(ctx, rows, seq_len)`.
6. `warm_requests(config, traffic, seconds) -> [(requests, prompt_len)]`:
   the groups of throw-away requests that, submitted one group after
   the other, execute every prefill and decode program the cell's
   schedule can reach.

What a family may need beside the first one's answers, and the harness
gives room for: keys of its own beside counts listed in `reduced` (a
part of the experts or of the vocabulary rows held on this chip), with
the published counts and the deployment stated in the file; a tree with
stacked experts, shared experts and a router; a reference told which
experts and rows are held; a cache of two kinds of layer, built from
serving keys that no other family has; prompts longer than the largest
prefill program. None of it asks for an edit outside the family's own
files.
"""

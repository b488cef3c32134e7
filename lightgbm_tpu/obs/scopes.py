"""The names the device trace carries: every ``jax.named_scope`` of the
package, in one tuple.

A scope adds a component to the ``op_name`` of each HLO instruction
traced inside it (``jit(scan_core)/while/body/lgb.wave_hist/dot_general``),
which the profiler stores per instruction in the trace's event metadata
(``tf_op``).  ``benchmark/scope_reduce.py`` gives each device event the
innermost name of this tuple found in its path; xprof's trace viewer and
op profile show the same names.  A scope changes metadata and nothing
else: the compiled program, and so the model, are the same with or
without it.

Call sites write the literal (``with jax.named_scope("lgb.wave_hist")``)
so that ``grep -rn named_scope lightgbm_tpu/`` lists them;
``tests/test_scopes.py`` holds the literals to this tuple.  The one
generated family is the wave histogram's stage names
(:func:`wave_hist_stage`): the call site and the tuple both take them
from that helper, and the test holds the call site to it.
"""

# a plan has at most 7 stages (stage_plan.derive_stage_plan: a ladder of
# <= 6 rungs and the closing stage); a hand-made longer one shares the
# last name among its stages past the seventh
MAX_STAGES = 8


def wave_hist_stage(stage: int) -> str:
    """The name of the wave histogram of stage ``stage`` of the plan
    (``lgb.wave_hist.s0`` holds the root wave), nested inside
    ``lgb.wave_hist``: each stage is a ``while_loop`` with a wave body of
    its own, so each stage's histogram is HLO of its own."""
    return f"lgb.wave_hist.s{min(int(stage), MAX_STAGES - 1)}"


SCOPES = (
    "lgb.gradient",      # objective gradients inside the fused scan
    "lgb.softmax_grad",  # ... multiclass: the iteration's softmax
    #                      normaliser and each class tree's g and h
    "lgb.bag_draw",      # bagging row mask / feature_fraction mask draws
    "lgb.goss_select",   # GOSS: a tree's top |g*h| rows and its sample
    "lgb.stat_cols",     # pad/valid masking, stat columns, quantisation
    "lgb.wave_hist",     # the wave histogram (einsum over bin strips)
    *(wave_hist_stage(i) for i in range(MAX_STAGES)),   # ... by stage
    "lgb.wave_gather",   # its live rows brought to the front (MXU compaction)
    "lgb.hist_state",    # sibling subtraction + per-leaf histogram writes
    "lgb.find_best",     # the gain scan over a histogram stack
    "lgb.find_best_cat",  # ... its categorical half (sorted subsets)
    "lgb.split_apply",   # top-k selection, leaf_id routing, record writes
    "lgb.stage_loop",    # a stage's while: carried-state copies, condition
    "lgb.leaf_refit",    # quantised runs: full-precision leaf refit
    "lgb.score_update",  # score += lr * value[leaf_id]
    "lgb.psum",          # cross-device sums (histograms, refit sums)
    "lgb.bag_sync",      # the host's bag drawn again after fused dispatches
    "lgb.traverse",      # packed serving traversal
    "lgb.bin",           # device-side binning
)

"""What the per-phase readers under ``layer_metrics/`` share: a phase of
the growth programs is one ``jax.named_scope`` of the program (or a family
of them), and its metric is that name's self seconds in the per-scope
reduction of the window's trace (``run["scopes"]``, which the kind makes
with ``scope_reduce.scopes`` over the program's own ``SCOPES``; a mean over
the device planes on a mesh) divided by the window's trees or dispatches.

A stage of the plan is a ``while_loop`` with a wave body of its own, and
its histogram carries ``lgb.wave_hist.s<i>`` inside ``lgb.wave_hist``
(the reduction gives an instruction its innermost name, so the stage rows
and what is left under the bare name add up to the whole histogram; the
compaction, ``lgb.wave_gather``, is inner still and in neither).

Everything here answers ``None`` rather than raise: a run without a
reduction, a window without busy time or trees, a program without the
name (the parent commit's, or an executable a compile cache handed back
under the names it was compiled with: the cache's key leaves metadata
out)."""

HIST = "lgb.wave_hist"
STAGE = HIST + ".s"


def reduction(run):
    """``run["scopes"]`` when the run has one with busy time, else
    ``None``."""
    scopes = run.get("scopes")
    if not scopes or not scopes.get("busy_s"):
        return None
    return scopes


def self_s(scopes, name):
    """Self seconds under ``name``, ``None`` when the trace never reaches
    it."""
    row = scopes.get(name)
    return row["self_s"] if isinstance(row, dict) and "self_s" in row \
        else None


def stages(scopes):
    """``[(stage index, self seconds)]`` of the stage names the trace
    reaches, by index."""
    return sorted((int(name[len(STAGE):]), self_s(scopes, name))
                  for name in scopes
                  if name.startswith(STAGE) and name[len(STAGE):].isdigit()
                  and self_s(scopes, name) is not None)


def ms_per(run, seconds, per="trees"):
    """``seconds`` as milliseconds a tree (or a dispatch) of the window;
    ``None`` without seconds or without any."""
    n = (run.get("window") or {}).get(per)
    if seconds is None or not n:
        return None
    return 1000.0 * seconds / n


def phase_ms(run, name, per="trees"):
    """The whole reader of a phase that is one name."""
    scopes = reduction(run)
    return None if scopes is None \
        else ms_per(run, self_s(scopes, name), per)

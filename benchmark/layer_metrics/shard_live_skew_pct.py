"""How far the fullest shard is above the mean, wave by wave: 100 x
(shards x ``grow.rows_live_max`` / ``grow.rows_live`` - 1) over the
window.  ``grow.rows_live`` sums the live rows every shard contracted,
``grow.rows_live_max`` those of the shard that had most in each wave; the
mesh ends every wave in a psum, so it runs at that shard's pace.  0 is an
even mesh.  The shard count is the program's own gauge ``shard.devices``.
``None`` when the program has no such counter (one chip, or a program
older than the counter)."""


def read(run):
    c = run["window_counters"]
    shards = (run.get("shard_gauges") or {}).get("shard.devices")
    if not c.get("grow.rows_live") or "grow.rows_live_max" not in c \
            or not shards:
        return None
    return 100.0 * (shards * c["grow.rows_live_max"] / c["grow.rows_live"]
                    - 1.0)

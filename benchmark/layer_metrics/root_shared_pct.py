"""Share of the window's class trees whose root histogram came from the
iteration's shared pass: 100 x ``grow.root_shared`` over
``grow.class_trees``, both counted by the program over the window.  A
softmax multiclass iteration takes every class's root histogram in one
chunk loop ahead of its class trees, whose root waves then contract
nothing; a quantised scan keeps a root wave a tree (0).  ``None`` when
the program has no such counter or grew no class tree."""


def read(run):
    c = run.get("window_counters") or {}
    if "grow.root_shared" not in c or not c.get("grow.class_trees"):
        return None
    return 100.0 * c["grow.root_shared"] / c["grow.class_trees"]

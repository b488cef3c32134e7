"""The plain reference's own arithmetic, on whatever device JAX has."""

import numpy as np

from benchmark.references import gbdt_binary as ref


def test_three_bfloat16_pieces_add_up_to_the_float32():
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    a = np.concatenate([rng.standard_normal(4096) * 10.0 ** rng.integers(
        -20, 20, 4096), [0.0, 1.0, -1.0, 1e-30, 3.0e38]]).astype(np.float32)
    parts = np.asarray(jax.jit(ref._split3)(jnp.asarray(a)[:, None])
                       .astype(jnp.float32))
    assert parts.shape == (a.size, 3)
    back = (parts[:, 0] + parts[:, 1]) + parts[:, 2]
    assert (back == a).all()


def test_floor_f32_keeps_the_float64_comparison():
    t = np.array([0.1, -0.1, 1.0, 1e-40, 123456.789], np.float64)
    f = ref.floor_f32(t)
    assert (f.astype(np.float64) <= t).all()
    up = np.nextafter(f, np.float32(np.inf)).astype(np.float64)
    assert (up > t).all()


def test_parse_dump_reads_the_json_form():
    leaf = lambda i, v, c: {"leaf_index": i, "leaf_value": v, "leaf_count": c}
    dump = {"objective": "binary sigmoid:1", "tree_info": [
        {"num_leaves": 3, "num_cat": 0, "tree_structure": {
            "split_index": 0, "split_feature": 1, "split_gain": 10.0,
            "threshold": 0.5, "decision_type": "<=", "default_left": True,
            "missing_type": "None",
            "left_child": {
                "split_index": 1, "split_feature": 0, "split_gain": 5.0,
                "threshold": 0.009532365016639233, "decision_type": "<=",
                "default_left": True, "missing_type": "None",
                "left_child": leaf(0, 0.1, 5), "right_child": leaf(2, 0.3, 7)},
            "right_child": leaf(1, -0.2, 6)}},
        {"num_leaves": 1, "num_cat": 0,
         "tree_structure": {"leaf_value": 0.05}}]}
    model = ref.parse_dump(dump)
    assert model["objective"] == "binary" and model["sigmoid"] == 1.0
    t0, t1 = model["trees"]
    assert t0["split_feature"].tolist() == [1, 0]
    assert t0["left_child"].tolist() == [1, -1]
    assert t0["right_child"].tolist() == [-2, -3]
    assert t0["leaf_count"].tolist() == [5, 6, 7]
    assert t0["leaf_value"].tolist() == [0.1, -0.2, 0.3]
    # the trained double, digit for digit
    assert t0["threshold"][1] == 0.009532365016639233
    assert t1["num_leaves"] == 1 and t1["leaf_value"].tolist() == [0.05]

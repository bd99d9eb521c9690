from .star_common import star


def reference(frames, literals, float_dtype="float64"):
    return star(frames, literals, ("i_manager_id", "manager"),
                ["i_brand_id", "i_brand"], ["s", "i_brand_id", "i_brand"],
                [False, True, True], float_dtype)

from .star_common import star


def reference(frames, literals, float_dtype="float64"):
    return star(frames, literals, ("i_manager_id", "manager"),
                ["d_year", "i_category_id", "i_category"],
                ["s", "d_year", "i_category_id", "i_category"],
                [False, True, True, True], float_dtype)

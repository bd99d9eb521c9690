from .star_common import star


def reference(frames, literals, float_dtype="float64"):
    return star(frames, literals, ("i_manufact_id", "manufact"),
                ["d_year", "i_brand_id", "i_brand"],
                ["d_year", "s", "i_brand_id", "i_brand"],
                [True, False, True, True], float_dtype)

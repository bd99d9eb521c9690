"""Process start to the window's start: import, data from the seed,
parquet, views, device cache, one warm call per statement shape (compile
included where the persistent cache has nothing)."""


def value(records, window_s, setup_s):
    return setup_s

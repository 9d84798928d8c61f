from repro.data.dedup import find_near_duplicates  # noqa: F401

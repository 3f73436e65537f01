"""Share of the projected leaves that the projection hook runs mesh-native,
under ``shard_map`` (``shard_map_codegen`` or ``shard_map_jnp``), not
vmapped under GSPMD: the program's ``projection_leaves`` counter, read by
the driver into ``counters``."""


def read(ctx):
    leaves = ctx["record"]["counters"].get("projection_leaves") or {}
    total = sum(leaves.values())
    if not total:
        return None
    native = sum(n for path, n in leaves.items()
                 if path.startswith("shard_map_"))
    return 100.0 * native / total

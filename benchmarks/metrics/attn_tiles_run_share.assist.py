"""Layer `serve_step`: of the turns the key-tile loops would run if
every layer read its slot's whole table as a full layer
(`attn_key_tiles_max`), the share the steps of the sending window did
run: the full layers' loops to the batch's longest row
(`attn_key_tiles_full`) plus the sliding layers' constant turns over
their short table (`attn_key_tiles_window`), in percent."""


def read(run):
    window = run["facts"].get("window") or {}
    ran = [window.get(k) for k in ("attn_key_tiles_full",
                                   "attn_key_tiles_window")]
    most = window.get("attn_key_tiles_max")
    if None in ran or not most:
        return None
    return 100.0 * sum(ran) / most

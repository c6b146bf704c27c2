"""Pages the paged decode kernel read (``decode_kv_pages_read``) times the
page size (the configuration's ``inference.page_size`` override), over the
live positions the same steps attended (``decode_kv_token_layers``): what
reading whole pages costs over reading only live K and V. 1 is nothing; a
program without the counter reads nothing."""


def read(obs):
    t = obs["timing"]
    pages, live = t.get("decode_kv_pages_read"), t.get("decode_kv_token_layers")
    size = [o.split("=")[1] for o in obs["config"]["orion"]["overrides"]
            if o.startswith("inference.page_size=")]
    if not pages or not live or not size:
        return None
    return int(size[-1]) * pages / live

"""kv_pages_peak_share: the most KV pages ever live at once (the page
pool's ``pages_high_water``, refreshed by ``scrape_gauges``) over the
pages the pool holds, less its reserved zero page (%)."""


def read(rec):
    c = rec["counters"]["end"]
    hw = [v for k, v in c.items() if k.startswith("pages_high_water{")]
    total = sum(n - 1 for n in rec["n_pages"].values())
    if not hw or not total:
        return None
    return 100.0 * sum(hw) / total

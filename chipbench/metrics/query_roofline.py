"""The queries' share of the chip's bandwidth roofline: the least bytes
the window's queries must read (``roofline.least_bytes``, from the
configuration) at the peak HBM bandwidth, over the device busy time the
trace shows for the same window."""


def read(record):
    tr = record.get("trace")
    if tr is None or tr.busy_s <= 0 or not record["queries"]:
        return None
    least = sum(record["least_bytes"][q["name"]] for q in record["queries"])
    return 100.0 * least / record["peaks"]["hbm_bytes_per_s"] / tr.busy_s

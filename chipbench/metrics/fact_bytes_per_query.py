"""Mean fact-table bytes one query streamed, as the program counts them
(``QueryResult.bytes_scanned``: the columns at their encoded widths)."""


def read(record):
    counts = [q["bytes_scanned"] for q in record["queries"]
              if q["bytes_scanned"] is not None]
    return sum(counts) / len(counts) if counts else None

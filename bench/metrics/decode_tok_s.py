"""decode_tok_s: output tokens returned in the window over its seconds."""

from lib import records


def read(rec):
    if rec.get("kind") != "serving":
        return None
    return records.tokens_in_window(rec) / rec["window"]["seconds"]

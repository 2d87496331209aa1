"""Mean duration of the named spans, in milliseconds."""


def read(args: dict, obs: dict):
    found = [obs["spans"][s] for s in args["spans"] if s in obs["spans"]]
    count = sum(c for c, _ in found)
    if not count:
        return None
    return 1e3 * sum(s for _, s in found) / count

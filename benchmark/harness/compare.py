"""The comparison that decides `correct`: every check of the window
against the plain reference (harness/reference.py), number by number.
Every number is exact, so every limit is 0.

- reference_golden_gap: the reference against the counts pinned in the
  configuration's file (a broken reference decides nothing);
- check_errors: checks that raised;
- generated_gap, unique_gap, depth_gap (exhaustive mix): expand, dedup
  and the BFS depth against the reference's;
- prefix_gap (first-discovery mix, where every level up to the first
  discovery fits in one batch, so that one step expands one level): the
  counts at exit against the reference's BFS stopped after that level;
  counts_over_space where a level spans batches (the exit then depends
  on the batch order): states counted beyond the whole space;
- discovery_set_gap: properties discovered that the reference does not
  discover, or (exhaustive) not discovered that it does, or (first
  discovery) no discovery at all;
- path_invalid_steps: steps of the discovery paths that the reference
  refuses (see reference.check_paths);
- path_depth_gap: a path's length against the least depth at which the
  reference discovers that property (BFS paths are shortest).
"""

from __future__ import annotations

LIMIT = 0


def _golden_gap(config: dict, ref) -> int:
    g = config["golden"]
    return (abs(ref.generated - g["generated"]) + abs(ref.unique - g["unique"])
            + abs(ref.depth - g["depth"])
            + len(ref.discovered ^ set(g["discoveries"])))


def first_prefix(ref, batch_size: int):
    """(generated, unique) at the first discovery of a check that expands
    one BFS level a step, or None where a level up to it spans batches
    or nothing is ever discovered."""
    if not ref.min_depth:
        return None
    first = min(ref.min_depth.values())
    if any(u > batch_size for u, _ in ref.levels[:first]):
        return None
    return ref.through(first)


def compare(config: dict, traffic: dict, checks: list, ref,
            path_invalid: list) -> tuple:
    """({name: (value, limit)}, failed checks). `path_invalid` holds the
    reference's invalid-step counts of every check's paths, in order."""
    exhaustive = traffic["answer"] == "exhaustive"
    prefix = (None if exhaustive
              else first_prefix(ref, config["spawn"]["batch_size"]))
    per_check = []
    k = 0
    for c in checks:
        n = {"check_errors": int(c.error is not None)}
        names = c.discovered
        if exhaustive:
            n["generated_gap"] = abs(c.generated - ref.generated)
            n["unique_gap"] = abs(c.unique - ref.unique)
            n["depth_gap"] = abs(c.depth - ref.depth)
            n["discovery_set_gap"] = len(names ^ ref.discovered)
        else:
            if prefix is not None:
                n["prefix_gap"] = (abs(c.generated - prefix[0])
                                   + abs(c.unique - prefix[1]))
            else:
                n["counts_over_space"] = (max(0, c.generated - ref.generated)
                                          + max(0, c.unique - ref.unique))
            n["discovery_set_gap"] = (len(names - ref.discovered)
                                      + int(not names and c.error is None))
        n["path_invalid_steps"] = sum(path_invalid[k:k + len(c.paths)])
        k += len(c.paths)
        n["path_depth_gap"] = max(
            [abs(len(states) - ref.min_depth[name])
             for name, states in c.paths.items() if name in ref.min_depth],
            default=0)
        per_check.append(n)
    numbers = {"reference_golden_gap": (_golden_gap(config, ref), LIMIT)}
    for name in per_check[0]:
        agg = sum if name in ("check_errors", "path_invalid_steps") else max
        numbers[name] = (agg(n[name] for n in per_check), LIMIT)
    failed = sum(any(v > LIMIT for v in n.values()) for n in per_check)
    return numbers, failed


def paths_of(checks: list) -> list:
    """Every check's (property, decoded states), in the order `compare`
    reads the invalid-step counts back."""
    return [(name, states) for c in checks for name, states in c.paths.items()]

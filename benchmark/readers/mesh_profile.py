"""Bytes through the mesh's collectives per statement, from each
statement's `MeshProfile` (`collective_bytes_by`: "<kind>/<purpose>" ->
bytes).  args: `kinds` (e.g. all_to_all, all_gather), `scale`."""


def read(run, kinds, scale=1e-6):
    per_statement = []
    for st in run["statements"]:
        profile = st.extra.get("mesh_profile")
        if not profile:
            continue
        by = profile.get("collective_bytes_by", {})
        per_statement.append(sum(
            b for k, b in by.items() if k.split("/")[0] in kinds
        ))
    if not per_statement:
        return None
    return sum(per_statement) / len(per_statement) * scale

"""``MixtureOfExperts``: ``matmul`` entries for the router (every row, all
``num_experts`` outputs), the shared expert (every row) and the routed
experts held here at the EXPECTED rows ``rows x top_k x experts_held /
num_experts`` (uniform routing; the rows a step really routed are in the
``fit.epoch.expert_load`` records)."""


def layers(node, in_shapes, out_shapes):
    p, name = node["param"], node["name"]
    rows, hidden = in_shapes[node["args"].index("data")]
    out = [{"op": "matmul", "name": f"{name}_router", "cin": hidden,
            "cout": int(p["num_experts"]), "rows": rows}]

    def gated(prefix, width, at):
        return [{"op": "matmul", "name": f"{name}_{prefix}{part}",
                 "cin": cin, "cout": cout, "rows": at}
                for part, cin, cout in (("gate", hidden, width),
                                        ("up", hidden, width),
                                        ("down", width, hidden))]

    if int(p["shared_width"]):
        out += gated("shared_", int(p["shared_width"]), rows)
    return out + gated("", int(p["expert_width"]),
                       rows * int(p["top_k"]) * int(p["experts_held"])
                       / int(p["num_experts"]))

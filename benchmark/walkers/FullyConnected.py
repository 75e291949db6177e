"""``FullyConnected``: ``fc`` when a sample is one row of its output,
``matmul`` with the rows a sample has there (a sequence flattened to
``(rows x positions, width)``) when it is several. Shapes are at batch 1."""


def layers(node, in_shapes, out_shapes):
    cout, cin = in_shapes[node["args"].index("weight")]
    rows = out_shapes[0][0]
    if rows == 1:
        return [{"op": "fc", "name": node["name"], "cin": cin, "cout": cout}]
    return [{"op": "matmul", "name": node["name"], "cin": cin, "cout": cout,
             "rows": rows}]

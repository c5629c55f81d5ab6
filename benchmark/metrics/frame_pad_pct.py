"""frame_pad_pct: cells the sharded bitfused kernel steps beyond the
board, as a share of the board's (layer: Kernels, ``ops/bitlife.py``
``plan_sharded_bits``).

The program's ``life.advance`` / ``life.segment`` spans of a sharded
bitfused advance carry ``board_cells`` (``ny * nx``) and ``frame_cells``
(the padded frame the kernel steps: the board and its periodic mirror
rows and columns). 100 x (sum of frame cells - sum of board cells) / sum
of board cells, over those spans. ``None`` where no span carries
them."""

STEP_SPANS = ("life.advance", "life.segment")


def read(ctx):
    counted = [s["attrs"] for s in ctx.spans or ()
               if s.get("kind") == "span" and s["name"] in STEP_SPANS
               and "frame_cells" in s.get("attrs", {})]
    board = sum(a["board_cells"] for a in counted)
    if board <= 0:
        return None
    return 100.0 * (sum(a["frame_cells"] for a in counted) - board) / board

"""Render the Mandelbrot set with Worker actors and write a P4 PBM
(≙ reference examples/mandelbrot writing its bitmap through files).

    python examples/mandelbrot.py [width] [out.pbm]
"""
import sys

sys.path.insert(0, ".")

from ponyc_tpu.models import mandelbrot  # noqa: E402


def main():
    width = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    out = sys.argv[2] if len(sys.argv) > 2 else "/tmp/mandelbrot.pbm"
    grid = mandelbrot.render(width, width)
    mandelbrot.write_pbm(out, grid, width)
    inside = sum(bin(b).count("1") for b in grid.tobytes())
    print(f"{width}x{width}: {inside} pixels in the set -> {out}")


if __name__ == "__main__":
    main()

"""Matmul precision of every contraction on the CoLA path.

On a TPU, an f32 contraction left at the default precision runs as a
single bfloat16 pass: on a v5e, (16, 16) @ (16, 8) with every entry of W
at 1/3 came out 0.2% off. CoLA's mixing has to preserve the mean exactly
(Lemma 1), and its certificates compare f32 gradients across nodes, so
that error compounds round over round. Measured on one v5e at the default:
after 20 rounds of the epsilon-sized run the Lemma-1 invariant residual
was 2.8e-2 (7e-7 at full precision), and the Fig.-1 lasso never
certified in 4,000 rounds (at full precision it certified at round 1360).
Every contraction that feeds the state, the gap or the certificate
therefore asks for full f32 precision; the CPU computes f32 either way.
Per group on the v5e, only the mixing products need it: at HIGH (three
bf16 passes) the invariant holds to 5e-5 but the lasso still never
certifies. The local solver at DEFAULT certifies 40 rounds earlier, and the
recorders at DEFAULT print the same digits; one constant keeps them all
at HIGHEST, for 5% of the steady round time.
"""
from jax import lax

MATMUL = lax.Precision.HIGHEST

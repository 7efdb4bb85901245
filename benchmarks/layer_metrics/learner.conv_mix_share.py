"""Share of device busy time under the scope `lfm2.conv.mix`
(models/lfm2_moe_q.Lfm2MoeQNet._conv: the two multiplicative gates and
the filter between them - what of a conv operator is NOT a matmul,
element-wise and bandwidth-bound over [tokens, 3 hidden]), forward,
recomputation and backward, in %, first chip. IT IS THE PART OF THE
MIXER'S TIME THAT KEPT THE MIXER'S NAME: where XLA puts B * x~ into
W_in's fusion or C * c into W_out's, that time reads under
`lfm2.conv.in` / `.out` (a fusion has one name), so this share is a
lower bound of the mixer's, and `kernels.short_conv_roofline` divides
by the whole of `lfm2.conv`.
benchmarks/harness/lfm2_scopes.py says how the scope's time is read; a
program without the scope leaves nothing to read."""

from benchmarks.harness import lfm2_scopes


def read(facts: dict) -> float | None:
    return lfm2_scopes.share_of_busy(facts, "lfm2.conv.mix")

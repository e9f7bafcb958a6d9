#!/usr/bin/perl
# predict.pl — Perl consumer of the compiled C ABI through the MXNetTPU XS
# binding (ref role: perl-package/ AI::MXNet inference;
# VERDICT r4 item 10: prove the ABI from one non-C language).
#
# Builds softmax(fc(data)) symbolically, loads known weights, runs a
# forward pass, and checks the probabilities against a pure-Perl
# reference computation.
use strict;
use warnings;
use FindBin;
use lib "$FindBin::Bin/blib";
use MXNetTPU;

printf "mxnet_tpu version %d (via Perl XS)\n", MXNetTPU::version();
my $nops = MXNetTPU::op_count();
die "too few ops: $nops" unless $nops > 200;
print "ops visible through ABI: $nops\n";

# --- net: SoftmaxOutput(FullyConnected(data, num_hidden=3)) ---
my ( $batch, $feat, $classes ) = ( 2, 4, 3 );
my $data  = MXNetTPU::sym_variable("data");
my $label = MXNetTPU::sym_variable("softmax_label");
my $fc    = MXNetTPU::sym_create( "FullyConnected", "num_hidden", "3",
    "fc", "$data" );
my $net = MXNetTPU::sym_create( "SoftmaxOutput", "", "", "softmax",
    "$fc,$label" );
my $args = MXNetTPU::sym_arguments($net);
die "unexpected args: $args"
  unless $args eq "data,fc_weight,fc_bias,softmax_label";

# --- arrays with known contents ---
my @x = map { 0.1 * $_ } 1 .. $batch * $feat;
my @w = map { 0.05 * ( $_ % 7 - 3 ) } 1 .. $classes * $feat;
my @b = ( 0.1, -0.2, 0.3 );
my @l = (0) x $batch;

my $a_x = MXNetTPU::nd_create("$batch,$feat");
my $a_w = MXNetTPU::nd_create("$classes,$feat");
my $a_b = MXNetTPU::nd_create("$classes");
my $a_l = MXNetTPU::nd_create("$batch");
MXNetTPU::nd_set( $a_x, pack( "f*", @x ) );
MXNetTPU::nd_set( $a_w, pack( "f*", @w ) );
MXNetTPU::nd_set( $a_b, pack( "f*", @b ) );
MXNetTPU::nd_set( $a_l, pack( "f*", @l ) );

my $exec = MXNetTPU::exec_bind( $net, "$a_x,$a_w,$a_b,$a_l" );
MXNetTPU::exec_forward($exec);
my @probs = unpack( "f*",
    MXNetTPU::nd_get( MXNetTPU::exec_out0($exec), $batch * $classes ) );

# --- pure-Perl reference: softmax(x @ w' + b) ---
for my $i ( 0 .. $batch - 1 ) {
    my @logits;
    for my $c ( 0 .. $classes - 1 ) {
        my $s = $b[$c];
        $s += $x[ $i * $feat + $_ ] * $w[ $c * $feat + $_ ]
          for 0 .. $feat - 1;
        push @logits, $s;
    }
    my $max = ( sort { $b <=> $a } @logits )[0];
    my @e   = map { exp( $_ - $max ) } @logits;
    my $z   = 0;
    $z += $_ for @e;
    for my $c ( 0 .. $classes - 1 ) {
        my $ref = $e[$c] / $z;
        my $got = $probs[ $i * $classes + $c ];
        # tolerance covers TPU execution (f32 matmuls at the chip's
        # default precision) when JAX_PLATFORMS does not pin the CPU
        die sprintf( "mismatch row %d class %d: %g vs %g",
            $i, $c, $got, $ref )
          if abs( $got - $ref ) > 2e-3;
    }
}
print "softmax probabilities match pure-Perl reference\n";
print "PERL PASS\n";

/* Native-speed CPU PDES of the exact flagship-bench semantics (tgen
 * request/response streams over the engine's TCP + netstack), serving as
 * the honest performance baseline the round-3 verdict asked for: a
 * thread_per_core-grade native stand-in (reference:
 * src/main/core/scheduler/thread_per_core.rs:12-115) instead of the
 * JAX-on-CPU strawman.
 *
 * This is a C port of OUR OWN scalar conformance oracle
 * (shadow_tpu/cpu_ref/tcp_ref.py + tgen_ref.py + netstack_ref.py + the
 * engine window loop of engine/round.py), bit-identical by construction:
 * the same threefry draws (validated against jax in
 * tests/test_native_baseline.py), the same integer TCP/shaping
 * arithmetic, the same (time, tie) total order. Counter equality with
 * the device engine on the same configuration is asserted by tests, so
 * the published baseline provably computes the same simulation.
 *
 * Input: a binary tables file (int32 n_nodes, int64 lat[n*n] ns,
 * float rel[n*n]) written by tools/native_baseline/run_native_baseline.py
 * write_tables; host->node mapping is i % n_nodes.
 *
 * Usage: tgen_pdes TABLES_FILE NUM_HOSTS SIM_NS [SEED] [RESP_BYTES]
 *        [PAUSE_NS] [RUNAHEAD_NS] [TX_REFILL] [RX_REFILL]
 */
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <time.h>

/* ---------------- threefry2x32 (jax-compatible) ---------------- */

typedef struct { uint32_t k0, k1; } Key;

static void threefry2x32(uint32_t k0, uint32_t k1, uint32_t x0, uint32_t x1,
                         uint32_t *o0, uint32_t *o1) {
    static const int rot[8] = {13, 15, 26, 6, 17, 29, 16, 24};
    uint32_t ks[3] = {k1, k0 ^ k1 ^ 0x1BD11BDAu, k0};
    x0 += k0;
    x1 += k1;
    for (int grp = 0; grp < 5; grp++) {
        for (int r = 0; r < 4; r++) {
            x0 += x1;
            int d = rot[(grp % 2) * 4 + r];
            x1 = (x1 << d) | (x1 >> (32 - d));
            x1 ^= x0;
        }
        x0 += ks[grp % 3];
        x1 += ks[(grp + 1) % 3] + (uint32_t)(grp + 1);
    }
    *o0 = x0;
    *o1 = x1;
}

static Key fold_in(Key k, uint32_t data) {
    Key r;
    threefry2x32(k.k0, k.k1, 0, data, &r.k0, &r.k1);
    return r;
}

/* jax.random.uniform(key, dtype=f32): bits = x0^x1 of threefry(key,(0,0));
 * float = bitcast(bits>>9 | 0x3f800000) - 1.0 */
static float uniform_f32(Key k) {
    uint32_t b0, b1;
    threefry2x32(k.k0, k.k1, 0, 0, &b0, &b1);
    uint32_t bits = ((b0 ^ b1) >> 9) | 0x3f800000u;
    float f;
    memcpy(&f, &bits, 4);
    return f - 1.0f;
}

/* ---------------- constants mirroring the engine ---------------- */

#define NS_PER_MS 1000000LL
#define NS_PER_SEC 1000000000LL
#define TIME_MAX 0x7fffffffffffffffLL

#define KIND_PACKET 0
#define KIND_TCP_TIMER 1 /* KIND_MODEL_BASE + 0 */
#define KIND_TCP_FLUSH 2 /* KIND_MODEL_BASE + 1 */
#define KIND_STREAM_START 9 /* TCP_KIND_USER_BASE */

#define LANE_PORTS 0
#define LANE_SEQ 1
#define LANE_ACK 2
#define LANE_FLAGS_LEN 3
#define LANE_WND 4
#define LANE_SACK_S 6
#define LANE_SACK_E 7
#define PAYLOAD_LANES 8

#define FLAG_FIN 0x01
#define FLAG_SYN 0x02
#define FLAG_RST 0x04
#define FLAG_ACK 0x10

#define AUX_SIZE_MASK ((1 << 24) - 1)
#define AUX_SHAPED_BIT (1 << 24)

/* TCP states */
enum { CLOSED, LISTEN, SYNSENT, SYNRECEIVED, ESTABLISHED, FINWAIT1,
       FINWAIT2, CLOSING, TIMEWAIT, CLOSEWAIT, LASTACK };

/* TcpParams (TGEN_TCP: 4 sockets, 1 s timewait; rest defaults) */
#define NSOCK 4
#define MSS 1460
#define HDR_BYTES 40
#define RCV_WND (256 * 1024)
#define INIT_CWND_SEGS 10
#define RTO_INIT NS_PER_SEC
#define RTO_MIN (200 * NS_PER_MS)
#define RTO_MAX (60 * NS_PER_SEC)
#define GRANULARITY NS_PER_MS
#define OOO_RANGES 4
#define SEGS_PER_FLUSH 4
#define PACKET_LANES (SEGS_PER_FLUSH + 1)
#define LOCAL_LANES 4 /* tcp flush + tcp timer + model flush + next-stream */
#define USE_SACK 1

/* netstack (netstack_ref.py spec) */
#define REFILL_INTERVAL_NS 1000000LL
#define CODEL_TARGET_NS 10000000LL
#define CODEL_INTERVAL_NS 100000000LL
#define MTU_BYTES 1500

/* tgen model */
#define TGEN_PORT 80
#define START_NS NS_PER_MS
#define REQ_BYTES 64

/* ---------------- event heap, keyed (time, tie) ---------------- */

typedef struct {
    int64_t time, tie;
    int32_t kind, aux;
    int32_t data[PAYLOAD_LANES];
} Ev;

typedef struct {
    Ev *a;
    int n, cap;
} Heap;

static inline int ev_lt(const Ev *x, const Ev *y) {
    if (x->time != y->time)
        return x->time < y->time;
    return x->tie < y->tie;
}

static void heap_push(Heap *h, Ev e) {
    if (h->n == h->cap) {
        h->cap = h->cap ? h->cap * 2 : 16;
        h->a = realloc(h->a, (size_t)h->cap * sizeof(Ev));
    }
    int i = h->n++;
    while (i > 0) {
        int p = (i - 1) / 2;
        if (!ev_lt(&e, &h->a[p]))
            break;
        h->a[i] = h->a[p];
        i = p;
    }
    h->a[i] = e;
}

static Ev heap_pop(Heap *h) {
    Ev top = h->a[0];
    Ev last = h->a[--h->n];
    int i = 0;
    for (;;) {
        int l = 2 * i + 1, r = l + 1, m = i;
        if (l < h->n && ev_lt(&h->a[l], &last))
            m = l;
        if (r < h->n && ev_lt(&h->a[r], m == i ? &last : &h->a[l]))
            m = r;
        if (m == i)
            break;
        h->a[i] = h->a[m];
        i = m;
    }
    h->a[i] = last;
    return top;
}

/* ---------------- pack_tie (events.py) ---------------- */

static inline int64_t pack_tie(int kind, int src_host, int64_t seq) {
    int64_t variant = kind != KIND_PACKET;
    return (variant << 62) | ((int64_t)src_host << 32) | (seq & 0xffffffffLL);
}

static inline int tie_src_host(int64_t tie) {
    return (int)((tie >> 32) & ((1 << 30) - 1));
}

/* ---------------- seq unwrap (transport/header.py) ---------------- */

static inline int64_t unwrap32(int64_t near, int32_t wire) {
    uint32_t delta_u = (uint32_t)wire - (uint32_t)near + 0x80000000u;
    return near + ((int64_t)delta_u - 0x80000000LL);
}

static inline int32_t to_wire32(int64_t seq) { return (int32_t)(uint32_t)seq; }

/* ---------------- per-host netstack state ---------------- */

typedef struct {
    int64_t refill, tokens, last;
} TB;

static int64_t tb_depart(TB *tb, int64_t now, int64_t size) {
    if (tb->refill <= 0)
        return now;
    int64_t cap = tb->refill + MTU_BYTES;
    int64_t iv = now > tb->last ? (now - tb->last) / REFILL_INTERVAL_NS : 0;
    int64_t cur = tb->tokens + iv * tb->refill;
    if (cur > cap)
        cur = cap;
    int64_t cur_last = tb->last + iv * REFILL_INTERVAL_NS;
    int64_t deficit = size - cur;
    if (deficit < 0)
        deficit = 0;
    int64_t k = (deficit + tb->refill - 1) / tb->refill;
    int64_t depart;
    if (deficit > 0) {
        depart = cur_last + k * REFILL_INTERVAL_NS;
        tb->last = depart;
    } else {
        depart = now;
        tb->last = cur_last;
    }
    tb->tokens = cur + k * tb->refill - size;
    return depart;
}

typedef struct {
    int64_t first_above, drop_next;
    int64_t count;
    int dropping;
} CoDel;

#include <math.h>
static int64_t codel_control_law(int64_t count) {
    int64_t c = count < 1 ? 1 : (count > 1024 ? 1024 : count);
    return (int64_t)(CODEL_INTERVAL_NS / sqrt((double)c));
}

static int codel_dequeue(CoDel *cd, int64_t now, int64_t sojourn,
                         int64_t backlog_bytes) {
    int below = sojourn < CODEL_TARGET_NS || backlog_bytes < MTU_BYTES;
    int ok_to_drop = 0;
    if (below)
        cd->first_above = -1;
    else if (cd->first_above < 0)
        cd->first_above = now + CODEL_INTERVAL_NS;
    else if (now >= cd->first_above)
        ok_to_drop = 1;

    if (cd->dropping) {
        if (!ok_to_drop) {
            cd->dropping = 0;
            return 0;
        }
        if (now >= cd->drop_next) {
            cd->count += 1;
            cd->drop_next += codel_control_law(cd->count);
            return 1;
        }
        return 0;
    }
    if (ok_to_drop) {
        cd->dropping = 1;
        int recent = (now - cd->drop_next) < CODEL_INTERVAL_NS;
        cd->count = (recent && cd->count > 2) ? cd->count - 2 : 1;
        cd->drop_next = now + codel_control_law(cd->count);
        return 1;
    }
    return 0;
}

/* ---------------- TCP slot (cpu_ref/tcp_ref.py Slot) ---------------- */

typedef struct {
    int st;
    int lport, rport, rhost;
    int64_t snd_una, snd_nxt, snd_max, snd_end;
    int fin_pending, fin_sent;
    int64_t peer_wnd;
    int64_t rcv_nxt, rcv_fin, delivered;
    int64_t ooo[OOO_RANGES][2];
    int64_t sacked[OOO_RANGES][2];
    int64_t rtx_mark;
    int64_t cwnd, ssthresh;
    int dupacks;
    int64_t recover;
    int in_rec;
    int64_t srtt, rttvar, rto;
    int rtt_pending;
    int64_t rtt_seq, rtt_ts, rto_expire;
    int backoff;
    int64_t tev_time;
    int64_t retransmits, segs_in, segs_out;
} Slot;

static void slot_reset(Slot *s) {
    s->snd_una = 0;
    s->snd_nxt = 0;
    s->snd_max = 0;
    s->snd_end = 1;
    s->fin_pending = 0;
    s->fin_sent = 0;
    s->peer_wnd = RCV_WND;
    s->rcv_nxt = 0;
    s->rcv_fin = -1;
    s->delivered = 0;
    for (int i = 0; i < OOO_RANGES; i++) {
        s->ooo[i][0] = s->ooo[i][1] = -1;
        s->sacked[i][0] = s->sacked[i][1] = -1;
    }
    s->rtx_mark = 0;
    s->cwnd = INIT_CWND_SEGS * MSS;
    s->ssthresh = 1LL << 40;
    s->dupacks = 0;
    s->recover = 0;
    s->in_rec = 0;
    s->srtt = -1;
    s->rttvar = 0;
    s->rto = RTO_INIT;
    s->rtt_pending = 0;
    s->rtt_seq = 0;
    s->rtt_ts = 0;
    s->rto_expire = TIME_MAX;
    s->backoff = 0;
}

static void slot_init(Slot *s) {
    memset(s, 0, sizeof(*s));
    s->st = CLOSED;
    s->rhost = -1;
    slot_reset(s);
    s->tev_time = TIME_MAX;
    s->retransmits = s->segs_in = s->segs_out = 0;
}

static void rtt_update(Slot *s, int64_t rtt) {
    if (s->srtt < 0) {
        s->rttvar = rtt / 2;
        s->srtt = rtt;
    } else {
        int64_t d = s->srtt - rtt;
        if (d < 0)
            d = -d;
        s->rttvar = (3 * s->rttvar + d) / 4;
        s->srtt = (7 * s->srtt + rtt) / 8;
    }
    int64_t g = 4 * s->rttvar;
    if (g < GRANULARITY)
        g = GRANULARITY;
    int64_t rto = s->srtt + g;
    if (rto < RTO_MIN)
        rto = RTO_MIN;
    if (rto > RTO_MAX)
        rto = RTO_MAX;
    s->rto = rto;
    s->rtt_pending = 0;
}

static void ooo_absorb(Slot *s) {
    for (int pass = 0; pass < OOO_RANGES; pass++) {
        int64_t reach = -1;
        int hits[OOO_RANGES], nh = 0;
        for (int i = 0; i < OOO_RANGES; i++) {
            if (s->ooo[i][0] >= 0 && s->ooo[i][0] <= s->rcv_nxt) {
                hits[nh++] = i;
                if (s->ooo[i][1] > reach)
                    reach = s->ooo[i][1];
            }
        }
        if (reach > s->rcv_nxt)
            s->rcv_nxt = reach;
        for (int i = 0; i < nh; i++)
            s->ooo[hits[i]][0] = s->ooo[hits[i]][1] = -1;
    }
}

static void range_insert(int64_t ranges[][2], int64_t s, int64_t e) {
    int64_t ms = s, me = e;
    int overlap[OOO_RANGES], nov = 0;
    for (int i = 0; i < OOO_RANGES; i++) {
        int64_t rs = ranges[i][0], re = ranges[i][1];
        if (rs >= 0 && s <= re && e >= rs) {
            overlap[nov++] = i;
            if (rs < ms)
                ms = rs;
            if (re > me)
                me = re;
        }
    }
    int ins = -1;
    for (int i = 0; i < OOO_RANGES && ins < 0; i++) {
        int is_ov = 0;
        for (int j = 0; j < nov; j++)
            if (overlap[j] == i)
                is_ov = 1;
        if (is_ov || ranges[i][0] < 0)
            ins = i;
    }
    for (int j = 0; j < nov; j++)
        ranges[overlap[j]][0] = ranges[overlap[j]][1] = -1;
    if (ins >= 0) {
        ranges[ins][0] = ms;
        ranges[ins][1] = me;
    }
}

/* first unsacked hole above `from` per the scoreboard */
static int64_t sack_hole(int64_t sacked[][2], int64_t from) {
    int64_t hole = from;
    for (int pass = 0; pass < OOO_RANGES; pass++) {
        int64_t reach = -1;
        for (int i = 0; i < OOO_RANGES; i++) {
            int64_t rs = sacked[i][0], re = sacked[i][1];
            if (rs >= 0 && rs <= hole && hole < re && re > reach)
                reach = re;
        }
        if (reach > hole)
            hole = reach;
    }
    return hole;
}

/* ---------------- simulation world ---------------- */

typedef struct {
    int h, n_nodes, clients, servers;
    int64_t *lat;   /* [n*n] */
    float *rel;     /* [n*n] */
    Heap *queues;   /* [h] */
    int64_t *seq;   /* [h] */
    uint32_t *ctr;  /* [h] */
    Key *keys;      /* [h] */
    Slot *slots;    /* [h*NSOCK] */
    TB *tx, *rx;
    CoDel *codel;
    int64_t *rx_backlog;
    /* counters */
    int64_t events_handled, packets_sent, packets_dropped, codel_dropped;
    int64_t bytes_sent, bytes_recv;
    int64_t *streams_started, *streams_done;
    int64_t bytes_down, resets, retransmits;
    /* model params */
    int64_t resp_bytes, pause_ns, runahead_ns, bootstrap_end_ns;
    int use_netstack;
    /* outbox */
    Ev *outbox;
    int *outbox_dst;
    int outbox_n, outbox_cap;
} World;

static void outbox_add(World *w, int dst, Ev e) {
    if (w->outbox_n == w->outbox_cap) {
        w->outbox_cap = w->outbox_cap ? w->outbox_cap * 2 : 1024;
        w->outbox = realloc(w->outbox, (size_t)w->outbox_cap * sizeof(Ev));
        w->outbox_dst = realloc(w->outbox_dst, (size_t)w->outbox_cap * sizeof(int));
    }
    w->outbox_dst[w->outbox_n] = dst;
    w->outbox[w->outbox_n++] = e;
}

static void mk_seg(int32_t *data, int lport, int rport, int64_t seq,
                   int64_t ack, int flags, int64_t plen, int64_t wnd,
                   int64_t sack_s, int64_t sack_e) {
    memset(data, 0, PAYLOAD_LANES * sizeof(int32_t));
    data[LANE_PORTS] = to_wire32(((int64_t)(lport & 0xffff) << 16) | (rport & 0xffff));
    data[LANE_SEQ] = to_wire32(seq);
    data[LANE_ACK] = to_wire32(ack);
    data[LANE_FLAGS_LEN] = (int32_t)((flags & 0xff) | (plen << 8));
    data[LANE_WND] = (int32_t)wnd;
    data[LANE_SACK_S] = to_wire32(sack_s);
    data[LANE_SACK_E] = to_wire32(sack_e);
}

/* ingress relay + CoDel; returns 1 if the event reaches the model */
static int ingress(World *w, int host, Ev *e) {
    if (!w->use_netstack || e->kind != KIND_PACKET)
        return 1;
    int64_t size = e->aux & AUX_SIZE_MASK;
    if (e->aux & AUX_SHAPED_BIT) {
        w->rx_backlog[host] -= size;
        w->bytes_recv += size;
        return 1;
    }
    int src = tie_src_host(e->tie);
    if (src == host || e->time < w->bootstrap_end_ns || w->rx[host].refill <= 0) {
        w->bytes_recv += size;
        return 1;
    }
    TB *tb = &w->rx[host];
    int64_t tok0 = tb->tokens, last0 = tb->last;
    int64_t ready = tb_depart(tb, e->time, size);
    int64_t sojourn = ready - e->time;
    if (codel_dequeue(&w->codel[host], ready, sojourn, w->rx_backlog[host])) {
        tb->tokens = tok0;
        tb->last = last0;
        w->codel_dropped++;
        return 0;
    }
    if (ready > e->time) {
        w->rx_backlog[host] += size;
        Ev d = *e;
        d.time = ready;
        d.aux = (int32_t)(size | AUX_SHAPED_BIT);
        heap_push(&w->queues[host], d);
        return 0;
    }
    w->bytes_recv += size;
    return 1;
}

typedef struct {
    int used;
    int dst;
    int32_t data[PAYLOAD_LANES];
    int64_t size;
} PLane;

typedef struct {
    int used;
    int64_t time;
    int kind;
    int slot;
} LLane;

static void handle(World *w, int host, Ev *e, int64_t window_end) {
    if (!ingress(w, host, e))
        return;
    w->events_handled++;
    Slot *slots = &w->slots[(size_t)host * NSOCK];
    int64_t t = e->time;
    int kind = e->kind;
    int32_t *data = e->data;

    /* ---- app_pre (tgen client stream start) ---- */
    int is_client = host < w->clients;
    int is_server = !is_client && host < w->clients + w->servers;
    int m_start = (kind == KIND_STREAM_START) && is_client;
    int can = 0, app_mask = 0, app_slot = 0;
    if (m_start) {
        int cslot = -1;
        for (int i = 0; i < NSOCK && cslot < 0; i++)
            if (slots[i].st == CLOSED)
                cslot = i;
        if (cslot >= 0) {
            can = 1;
            int lport = 40000 + (int)(w->streams_started[host] % 20000);
            int server = w->clients +
                         (int)((host + w->streams_started[host]) % w->servers);
            Slot *s = &slots[cslot];
            /* app_connect from CLOSED */
            slot_reset(s);
            s->st = SYNSENT;
            s->lport = lport;
            s->rport = TGEN_PORT;
            s->rhost = server;
            s->snd_end += REQ_BYTES; /* app_write */
            w->streams_started[host]++;
            app_mask = 1;
            app_slot = cslot;
        }
    }
    int64_t bytes_before = 0;
    for (int i = 0; i < NSOCK; i++)
        bytes_before += slots[i].delivered;

    LLane l_lanes[LOCAL_LANES];
    PLane p_lanes[PACKET_LANES];
    memset(l_lanes, 0, sizeof(l_lanes));
    memset(p_lanes, 0, sizeof(p_lanes));

    int m_rx = kind == KIND_PACKET;
    int m_tmr = kind == KIND_TCP_TIMER;
    int m_flush = kind == KIND_TCP_FLUSH;

    int sig_est = 0, sig_fin = 0, sig_closed = 0, sig_rst = 0;
    int need_ack = 0, rtx_hole = 0, m_act = 0, m_stray = 0;
    Slot *act = NULL;
    int act_i = 0;
    int32_t stray_rst[PAYLOAD_LANES];
    int src = tie_src_host(e->tie);

    if (m_rx) {
        int sport = (data[LANE_PORTS] >> 16) & 0xffff;
        int dport = data[LANE_PORTS] & 0xffff;
        int flags = data[LANE_FLAGS_LEN] & 0xff;
        int64_t plen = ((int64_t)(uint32_t)data[LANE_FLAGS_LEN] >> 8) & 0xffffff;
        int64_t wnd = data[LANE_WND];
        int f_syn = !!(flags & FLAG_SYN), f_ack = !!(flags & FLAG_ACK);
        int f_fin = !!(flags & FLAG_FIN), f_rst = !!(flags & FLAG_RST);

        int rx_exact_i = -1, rx_lsn_i = -1;
        for (int i = 0; i < NSOCK; i++) {
            Slot *s = &slots[i];
            if (rx_exact_i < 0 && s->st != CLOSED && s->st != LISTEN &&
                s->lport == dport && s->rhost == src && s->rport == sport)
                rx_exact_i = i;
            if (rx_lsn_i < 0 && s->st == LISTEN && s->lport == dport)
                rx_lsn_i = i;
        }
        int rx_listen = rx_exact_i < 0 && rx_lsn_i >= 0;
        int rx_match = rx_exact_i >= 0 || rx_lsn_i >= 0;

        int m_spawn = 0;
        if (rx_listen && f_syn && !f_ack) {
            int child_i = -1;
            for (int i = 0; i < NSOCK && child_i < 0; i++)
                if (slots[i].st == CLOSED)
                    child_i = i;
            if (child_i >= 0) {
                m_spawn = 1;
                Slot *cs = &slots[child_i];
                slot_reset(cs);
                cs->st = SYNRECEIVED;
                cs->lport = dport;
                cs->rport = sport;
                cs->rhost = src;
                cs->rcv_nxt = 1;
                cs->peer_wnd = wnd;
                act = cs;
                act_i = child_i;
            }
        }
        if (rx_exact_i >= 0) {
            act = &slots[rx_exact_i];
            act_i = rx_exact_i;
        }
        m_act = (rx_exact_i >= 0) || m_spawn;
        if (m_act) {
            Slot *v = act;
            v->segs_in++;
            int64_t abs_seq = unwrap32(v->rcv_nxt, data[LANE_SEQ]);
            int64_t abs_ack = unwrap32(v->snd_una, data[LANE_ACK]);

            int m_rst = f_rst && v->st != CLOSED;
            if (m_rst) {
                v->st = CLOSED;
                v->rto_expire = TIME_MAX;
                sig_rst = 1;
            }
            int live = !m_rst;

            if (live && v->st == SYNSENT && f_syn && f_ack && abs_ack >= 1) {
                v->st = ESTABLISHED;
                v->rcv_nxt = 1;
                v->snd_una = 1;
                v->peer_wnd = wnd;
                v->rto_expire = TIME_MAX;
                v->backoff = 0;
                if (v->rtt_pending)
                    rtt_update(v, t - v->rtt_ts);
                sig_est = 1;
                need_ack = 1;
            } else if (live && v->st == SYNRECEIVED && f_ack && !f_syn &&
                       abs_ack >= 1) {
                v->st = ESTABLISHED;
                if (v->snd_una < 1)
                    v->snd_una = 1;
                v->peer_wnd = wnd;
                v->rto_expire = TIME_MAX;
                v->backoff = 0;
                if (v->rtt_pending)
                    rtt_update(v, t - v->rtt_ts);
                sig_est = 1;
            }

            int datast = v->st == ESTABLISHED || v->st == FINWAIT1 ||
                         v->st == FINWAIT2 || v->st == CLOSING ||
                         v->st == TIMEWAIT || v->st == CLOSEWAIT ||
                         v->st == LASTACK;
            int m_data_st = live && datast;

            /* ---- ACK processing ---- */
            int m_ackp = m_data_st && f_ack;
            int64_t snd_una_pre = v->snd_una;
            int valid_ack = m_ackp && v->snd_una < abs_ack && abs_ack <= v->snd_max;
            int64_t acked = valid_ack ? abs_ack - v->snd_una : 0;
            if (valid_ack && v->rtt_pending && abs_ack >= v->rtt_seq)
                rtt_update(v, t - v->rtt_ts);
            int full_ack = valid_ack && v->in_rec && abs_ack >= v->recover;
            int part_ack = valid_ack && v->in_rec && !full_ack;
            int ss = valid_ack && !v->in_rec && v->cwnd < v->ssthresh;
            int ca = valid_ack && !v->in_rec && !ss;
            int64_t cwnd1 = ss ? v->cwnd + (acked < MSS ? acked : MSS) : v->cwnd;
            if (ca) {
                int64_t denom = cwnd1 > 1 ? cwnd1 : 1;
                int64_t inc = (int64_t)MSS * MSS / denom;
                cwnd1 += inc > 1 ? inc : 1;
            }
            if (full_ack)
                cwnd1 = v->ssthresh;
            if (part_ack) {
                cwnd1 = cwnd1 - acked + MSS;
                if (cwnd1 < MSS)
                    cwnd1 = MSS;
            }
            rtx_hole = part_ack;
            if (valid_ack) {
                v->snd_una = abs_ack;
                if (v->snd_nxt < abs_ack)
                    v->snd_nxt = abs_ack;
                v->dupacks = 0;
                v->backoff = 0;
            }
            if (full_ack)
                v->in_rec = 0;
            v->cwnd = cwnd1;
            if (m_ackp)
                v->peer_wnd = wnd;
            int outstanding = v->snd_una < v->snd_max;
            if (valid_ack)
                v->rto_expire = outstanding ? t + v->rto : TIME_MAX;

            if (USE_SACK) {
                int32_t ss_w = data[LANE_SACK_S], se_w = data[LANE_SACK_E];
                if (m_ackp && ss_w != se_w)
                    range_insert(v->sacked, unwrap32(v->snd_una, ss_w),
                                 unwrap32(v->snd_una, se_w));
                if (m_ackp)
                    for (int i = 0; i < OOO_RANGES; i++)
                        if (v->sacked[i][0] >= 0 && v->sacked[i][1] <= v->snd_una)
                            v->sacked[i][0] = v->sacked[i][1] = -1;
            }

            int dup = m_ackp && !valid_ack && abs_ack == snd_una_pre &&
                      plen == 0 && !f_fin && outstanding;
            int dup3 = dup && v->dupacks == 2 && !v->in_rec;
            int64_t flight = v->snd_max - v->snd_una;
            if (dup)
                v->dupacks++;
            if (dup3) {
                int64_t th = flight / 2;
                if (th < 2 * MSS)
                    th = 2 * MSS;
                v->ssthresh = th;
                v->cwnd = th + 3 * MSS;
                v->recover = v->snd_max;
                v->in_rec = 1;
            } else if (dup && v->in_rec) {
                v->cwnd += MSS;
            }
            if (USE_SACK) {
                int64_t hole_rx = sack_hole(v->sacked, v->snd_una);
                int sack_any = 0;
                for (int i = 0; i < OOO_RANGES; i++)
                    if (v->sacked[i][0] >= 0)
                        sack_any = 1;
                int march = dup && v->in_rec && sack_any &&
                            hole_rx > v->rtx_mark && hole_rx < v->snd_max;
                rtx_hole = rtx_hole || dup3 || march;
                if (full_ack)
                    v->rtx_mark = 0;
                else if (rtx_hole)
                    v->rtx_mark = hole_rx;
            } else {
                rtx_hole = rtx_hole || dup3;
            }

            int fin_acked = m_ackp && v->fin_sent && v->snd_una >= v->snd_end + 1;
            if (fin_acked) {
                if (v->st == FINWAIT1)
                    v->st = FINWAIT2;
                else if (v->st == CLOSING)
                    v->st = TIMEWAIT;
                else if (v->st == LASTACK)
                    v->st = CLOSED;
            }
            sig_closed = sig_closed || (fin_acked && v->st == CLOSED);
            int enter_tw_ack = fin_acked && v->st == TIMEWAIT;

            /* ---- in-window data ---- */
            int m_seg = m_data_st && plen > 0;
            int64_t seg_s = abs_seq, seg_e = abs_seq + plen;
            int acceptable = m_seg && seg_e > v->rcv_nxt &&
                             seg_s <= v->rcv_nxt + RCV_WND;
            int in_order = acceptable && seg_s <= v->rcv_nxt;
            int ooo_seg = acceptable && !in_order;
            int64_t old_rcv = v->rcv_nxt;
            if (in_order) {
                v->rcv_nxt = seg_e;
                ooo_absorb(v);
            }
            if (ooo_seg)
                range_insert(v->ooo, seg_s, seg_e);
            if (m_seg) {
                v->delivered += v->rcv_nxt - old_rcv;
                need_ack = 1;
            }

            /* ---- peer FIN ---- */
            int m_finp = m_data_st && f_fin;
            if (m_finp && v->rcv_fin < 0)
                v->rcv_fin = seg_e;
            int fin_now = m_data_st && v->rcv_fin >= 0 && v->rcv_nxt == v->rcv_fin;
            int enter_tw_fin = 0;
            if (fin_now) {
                v->rcv_nxt++;
                if (v->st == ESTABLISHED)
                    v->st = CLOSEWAIT;
                else if (v->st == FINWAIT2) {
                    enter_tw_fin = 1;
                    v->st = TIMEWAIT;
                } else if (v->st == FINWAIT1)
                    v->st = CLOSING;
                sig_fin = 1;
            }
            if (m_finp)
                need_ack = 1;
            if (enter_tw_ack || enter_tw_fin)
                v->rto_expire = t + 1 * NS_PER_SEC; /* TGEN_TCP timewait */
        } else if (!rx_match && !f_rst) {
            m_stray = 1;
            int64_t ack_for = unwrap32(0, data[LANE_ACK]);
            int64_t abs_seq0 = unwrap32(0, data[LANE_SEQ]);
            mk_seg(stray_rst, dport, sport, ack_for,
                   abs_seq0 + plen + (f_syn ? 1 : 0) + (f_fin ? 1 : 0),
                   FLAG_RST | FLAG_ACK, 0, 0, 0, 0);
        }
    }

    if (m_tmr) {
        int t_slot = data[0];
        if (t_slot < 0)
            t_slot = 0;
        if (t_slot > NSOCK - 1)
            t_slot = NSOCK - 1;
        Slot *sw = &slots[t_slot];
        if (t >= sw->tev_time)
            sw->tev_time = TIME_MAX;
        int fired = t >= sw->rto_expire && sw->rto_expire < TIME_MAX;
        if (fired && sw->st == TIMEWAIT) {
            sw->st = CLOSED;
            sw->rto_expire = TIME_MAX;
            sig_closed = 1;
        } else if (fired && sw->snd_una < sw->snd_max) {
            int64_t flight_w = sw->snd_max - sw->snd_una;
            int64_t th = flight_w / 2;
            if (th < 2 * MSS)
                th = 2 * MSS;
            sw->ssthresh = th;
            sw->cwnd = MSS;
            sw->snd_nxt = sw->snd_una;
            sw->in_rec = 0;
            sw->dupacks = 0;
            sw->rto = sw->rto * 2 < RTO_MAX ? sw->rto * 2 : RTO_MAX;
            sw->backoff++;
            sw->rtt_pending = 0;
            sw->rto_expire = TIME_MAX;
            if (USE_SACK) {
                for (int i = 0; i < OOO_RANGES; i++)
                    sw->sacked[i][0] = sw->sacked[i][1] = -1;
                sw->rtx_mark = 0;
            }
        }
    }

    /* ---------------- OUTPUT pass ---------------- */
    int out_i;
    if (m_act)
        out_i = act_i;
    else if (m_tmr || m_flush) {
        out_i = data[0];
        if (out_i < 0)
            out_i = 0;
        if (out_i > NSOCK - 1)
            out_i = NSOCK - 1;
    } else
        out_i = app_slot;
    int out_mask = m_act || m_tmr || m_flush || app_mask;
    rtx_hole = rtx_hole && m_act;

    if (out_mask) {
        Slot *o = &slots[out_i];
        int m_syn_out = (o->st == SYNSENT || o->st == SYNRECEIVED) && o->snd_nxt == 0;
        int syn_flags = o->st == SYNRECEIVED ? (FLAG_SYN | FLAG_ACK) : FLAG_SYN;
        int syn_is_rtx = m_syn_out && o->snd_max > 0;
        int can_send = o->st == ESTABLISHED || o->st == CLOSEWAIT ||
                       o->st == FINWAIT1 || o->st == CLOSING || o->st == LASTACK;
        int64_t cwin = o->cwnd < o->peer_wnd ? o->cwnd : o->peer_wnd;
        int64_t wnd_lim = o->snd_una + cwin;
        int64_t fin_lim = o->snd_end + (o->fin_pending ? 1 : 0);

        int64_t hole = USE_SACK ? sack_hole(o->sacked, o->snd_una) : o->snd_una;
        int is_first_rtx = rtx_hole && can_send;
        int64_t cursor = is_first_rtx ? hole : o->snd_nxt;
        if (is_first_rtx)
            o->rtt_pending = 0; /* Karn */
        int sent_any = 0, fin_goes = 0;
        int64_t rtx_count = 0;

        for (int i = 0; i < SEGS_PER_FLUSH; i++) {
            int64_t room = o->snd_end;
            if (wnd_lim < room)
                room = wnd_lim;
            if (cursor + MSS < room)
                room = cursor + MSS;
            int64_t dlen = room - cursor;
            if (dlen < 0)
                dlen = 0;
            int send_data = can_send && dlen > 0;
            int send_fin = can_send && !send_data && o->fin_pending &&
                           cursor == o->snd_end && cursor + 1 <= wnd_lim &&
                           !fin_goes;
            int lane_used = send_data || send_fin;
            int64_t seq_w = cursor;
            int lflags = send_fin ? (FLAG_FIN | FLAG_ACK)
                                  : (send_data ? FLAG_ACK : 0);
            if (i == 0 && m_syn_out) {
                lane_used = 1;
                seq_w = 0;
                lflags = syn_flags;
            }
            int64_t lplen = send_data ? dlen : 0;
            if (lane_used) {
                p_lanes[i].used = 1;
                p_lanes[i].dst = o->rhost;
                mk_seg(p_lanes[i].data, o->lport, o->rport, seq_w, o->rcv_nxt,
                       lflags, lplen, RCV_WND, 0, 0);
                p_lanes[i].size = lplen + HDR_BYTES;
            }
            int is_rtx = send_data && cursor < o->snd_max;
            if (i == 0)
                is_rtx = is_rtx || is_first_rtx || syn_is_rtx;
            rtx_count += is_rtx ? 1 : 0;
            int fresh = send_data && cursor >= o->snd_max && !is_rtx;
            if (fresh && !o->rtt_pending) {
                o->rtt_pending = 1;
                o->rtt_seq = cursor + dlen;
                o->rtt_ts = t;
            }
            cursor += (send_data ? dlen : 0) + (send_fin ? 1 : 0);
            if (i == 0 && is_first_rtx && cursor < o->snd_nxt)
                cursor = o->snd_nxt;
            fin_goes = fin_goes || send_fin;
            sent_any = sent_any || lane_used;
        }

        if (can_send && o->snd_nxt < cursor)
            o->snd_nxt = cursor;
        if (m_syn_out)
            o->snd_nxt = 1;
        if (o->snd_max < o->snd_nxt)
            o->snd_max = o->snd_nxt;
        if (fin_goes) {
            if (o->st == ESTABLISHED)
                o->st = FINWAIT1;
            else if (o->st == CLOSEWAIT)
                o->st = LASTACK;
        }
        if (m_syn_out && !o->rtt_pending && !syn_is_rtx) {
            o->rtt_pending = 1;
            o->rtt_seq = 1;
            o->rtt_ts = t;
        }
        int outstanding_o = (o->snd_una < o->snd_max) || m_syn_out;
        if (outstanding_o && o->rto_expire >= TIME_MAX && (sent_any || m_syn_out))
            o->rto_expire = t + o->rto;
        int64_t lim = fin_lim < wnd_lim ? fin_lim : wnd_lim;
        int more = can_send && lim > cursor;
        int need_tev = o->rto_expire < o->tev_time;
        if (need_tev)
            o->tev_time = o->rto_expire;
        if (fin_goes)
            o->fin_sent = 1;
        o->retransmits += rtx_count;
        w->retransmits += rtx_count;
        for (int i = 0; i < SEGS_PER_FLUSH; i++)
            o->segs_out += p_lanes[i].used;

        if (more) {
            l_lanes[0].used = 1;
            l_lanes[0].time = t;
            l_lanes[0].kind = KIND_TCP_FLUSH;
            l_lanes[0].slot = out_i;
        }
        if (need_tev) {
            l_lanes[1].used = 1;
            l_lanes[1].time = o->rto_expire;
            l_lanes[1].kind = KIND_TCP_TIMER;
            l_lanes[1].slot = out_i;
        }
    }

    /* control lane (ACK / stray RST) */
    if (m_act && need_ack) {
        Slot *va = &slots[act_i];
        int64_t ss = 0, se = 0;
        if (USE_SACK) {
            int64_t bs = -1, be = -1;
            for (int i = 0; i < OOO_RANGES; i++) {
                int64_t rs = va->ooo[i][0], re = va->ooo[i][1];
                if (rs >= 0 && (bs < 0 || rs < bs || (rs == bs && re < be))) {
                    bs = rs;
                    be = re;
                }
            }
            if (bs >= 0) {
                ss = bs;
                se = be;
            }
        }
        PLane *pl = &p_lanes[SEGS_PER_FLUSH];
        pl->used = 1;
        pl->dst = va->rhost;
        mk_seg(pl->data, va->lport, va->rport, va->snd_nxt, va->rcv_nxt,
               FLAG_ACK, 0, RCV_WND, ss, se);
        pl->size = HDR_BYTES;
    } else if (m_stray) {
        PLane *pl = &p_lanes[SEGS_PER_FLUSH];
        pl->used = 1;
        pl->dst = src;
        memcpy(pl->data, stray_rst, sizeof(stray_rst));
        pl->size = HDR_BYTES;
    }

    /* ---- app_post (tgen) ---- */
    {
        int sig_slot = out_mask ? out_i : -1;
        int sslot = sig_slot >= 0 ? sig_slot : 0;
        Slot *v = &slots[sslot];
        int m_resp = is_server && sig_slot >= 0 && v->st == ESTABLISHED &&
                     v->delivered >= REQ_BYTES && v->snd_end == 1;
        if (m_resp) {
            /* app_write + app_close */
            if (v->st != CLOSED && v->st != LISTEN && !v->fin_pending)
                v->snd_end += w->resp_bytes;
            if (v->st != CLOSED && v->st != LISTEN)
                v->fin_pending = 1;
        }
        int m_eof = sig_fin && is_client;
        if (m_eof && v->st != CLOSED && v->st != LISTEN)
            v->fin_pending = 1;
        int m_done = sig_closed && is_client;
        if (m_done)
            w->streams_done[host]++;
        if (is_client) {
            int64_t now_del = 0;
            for (int i = 0; i < NSOCK; i++)
                now_del += slots[i].delivered;
            w->bytes_down += now_del - bytes_before;
        }
        if (sig_rst)
            w->resets++;
        if (m_resp || m_eof) {
            l_lanes[2].used = 1;
            l_lanes[2].time = t;
            l_lanes[2].kind = KIND_TCP_FLUSH;
            l_lanes[2].slot = sslot;
        }
        if (m_done || (m_start && !can)) {
            l_lanes[3].used = 1;
            l_lanes[3].time = t + w->pause_ns;
            l_lanes[3].kind = KIND_STREAM_START;
            l_lanes[3].slot = 0;
        }
    }

    /* ---- engine wrap: seq minting, egress, loss ---- */
    uint32_t base_ctr = w->ctr[host];
    for (int li = 0; li < LOCAL_LANES; li++) {
        if (!l_lanes[li].used)
            continue;
        Ev le;
        memset(&le, 0, sizeof(le));
        le.time = l_lanes[li].time;
        le.kind = l_lanes[li].kind;
        le.tie = pack_tie(le.kind, host, w->seq[host]++);
        le.data[0] = l_lanes[li].slot;
        heap_push(&w->queues[host], le);
    }
    int hnode = host % w->n_nodes;
    for (int pi = 0; pi < PACKET_LANES; pi++) {
        if (!p_lanes[pi].used)
            continue;
        int dst = p_lanes[pi].dst;
        if (dst < 0)
            dst = 0;
        if (dst > w->h - 1)
            dst = w->h - 1;
        int dnode = dst % w->n_nodes;
        int64_t lat = w->lat[hnode * w->n_nodes + dnode];
        float rel = w->rel[hnode * w->n_nodes + dnode];
        float loss_u = uniform_f32(fold_in(w->keys[host], base_ctr + (uint32_t)pi));
        if (lat >= TIME_MAX)
            continue;
        int64_t dep = t;
        if (w->use_netstack) {
            int exempt = dst == host || t < w->bootstrap_end_ns;
            if (!exempt)
                dep = tb_depart(&w->tx[host], t, p_lanes[pi].size);
        }
        if (loss_u < rel) {
            int64_t deliver = dep + lat;
            if (deliver < window_end)
                deliver = window_end;
            Ev pe;
            memset(&pe, 0, sizeof(pe));
            pe.time = deliver;
            pe.kind = KIND_PACKET;
            pe.tie = pack_tie(KIND_PACKET, host, w->seq[host]++);
            memcpy(pe.data, p_lanes[pi].data, sizeof(pe.data));
            pe.aux = (int32_t)(p_lanes[pi].size & AUX_SIZE_MASK);
            outbox_add(w, dst, pe);
            w->packets_sent++;
            if (w->use_netstack)
                w->bytes_sent += p_lanes[pi].size;
        } else {
            w->packets_dropped++;
        }
    }
    w->ctr[host] = base_ctr + PACKET_LANES;
}

int main(int argc, char **argv) {
    if (argc < 4) {
        fprintf(stderr, "usage: %s TABLES H SIM_NS [SEED] [RESP] [PAUSE] "
                        "[RUNAHEAD] [TX_REFILL] [RX_REFILL]\n", argv[0]);
        return 2;
    }
    World w;
    memset(&w, 0, sizeof(w));
    FILE *f = fopen(argv[1], "rb");
    if (!f) {
        perror("tables");
        return 2;
    }
    int32_t n;
    if (fread(&n, 4, 1, f) != 1)
        return 2;
    w.n_nodes = n;
    w.lat = malloc((size_t)n * n * 8);
    w.rel = malloc((size_t)n * n * 4);
    if (fread(w.lat, 8, (size_t)n * n, f) != (size_t)n * n)
        return 2;
    if (fread(w.rel, 4, (size_t)n * n, f) != (size_t)n * n)
        return 2;
    fclose(f);

    w.h = atoi(argv[2]);
    int64_t end_ns = atoll(argv[3]);
    int64_t seed = argc > 4 ? atoll(argv[4]) : 7;
    w.resp_bytes = argc > 5 ? atoll(argv[5]) : 100000;
    w.pause_ns = argc > 6 ? atoll(argv[6]) : 500 * NS_PER_MS;
    w.runahead_ns = argc > 7 ? atoll(argv[7]) : 2 * NS_PER_MS;
    int64_t tx_refill = argc > 8 ? atoll(argv[8]) : 12500; /* 100 Mbit */
    int64_t rx_refill = argc > 9 ? atoll(argv[9]) : 12500;
    w.use_netstack = 1;
    w.clients = w.h / 2;
    w.servers = w.h - w.clients;

    w.queues = calloc((size_t)w.h, sizeof(Heap));
    w.seq = calloc((size_t)w.h, 8);
    w.ctr = calloc((size_t)w.h, 4);
    w.keys = malloc((size_t)w.h * sizeof(Key));
    w.slots = malloc((size_t)w.h * NSOCK * sizeof(Slot));
    w.tx = malloc((size_t)w.h * sizeof(TB));
    w.rx = malloc((size_t)w.h * sizeof(TB));
    w.codel = malloc((size_t)w.h * sizeof(CoDel));
    w.rx_backlog = calloc((size_t)w.h, 8);
    w.streams_started = calloc((size_t)w.h, 8);
    w.streams_done = calloc((size_t)w.h, 8);

    Key base = {(uint32_t)((uint64_t)seed >> 32), (uint32_t)seed};
    for (int i = 0; i < w.h; i++) {
        w.keys[i] = fold_in(base, (uint32_t)i);
        for (int sck = 0; sck < NSOCK; sck++)
            slot_init(&w.slots[(size_t)i * NSOCK + sck]);
        w.tx[i].refill = tx_refill;
        w.tx[i].tokens = tx_refill + MTU_BYTES;
        w.tx[i].last = 0;
        w.rx[i].refill = rx_refill;
        w.rx[i].tokens = rx_refill + MTU_BYTES;
        w.rx[i].last = 0;
        w.codel[i].first_above = -1;
        w.codel[i].drop_next = 0;
        w.codel[i].count = 0;
        w.codel[i].dropping = 0;
    }
    /* tgen init: servers listen on slot 0; clients bootstrap a stream start */
    for (int i = w.clients; i < w.clients + w.servers; i++) {
        Slot *s = &w.slots[(size_t)i * NSOCK];
        s->st = LISTEN;
        s->lport = TGEN_PORT;
    }
    for (int i = 0; i < w.clients; i++) {
        Ev e;
        memset(&e, 0, sizeof(e));
        e.time = START_NS;
        e.kind = KIND_STREAM_START;
        e.tie = pack_tie(KIND_STREAM_START, i, w.seq[i]++);
        heap_push(&w.queues[i], e);
    }

    struct timespec t0, t1;
    clock_gettime(CLOCK_MONOTONIC, &t0);

    /* the conservative window loop (engine/round.py run_until semantics) */
    for (;;) {
        int64_t start = TIME_MAX;
        for (int i = 0; i < w.h; i++)
            if (w.queues[i].n && w.queues[i].a[0].time < start)
                start = w.queues[i].a[0].time;
        if (start >= end_ns)
            break;
        int64_t window_end = start + w.runahead_ns;
        if (window_end > end_ns)
            window_end = end_ns;
        w.outbox_n = 0;
        for (int i = 0; i < w.h; i++) {
            Heap *q = &w.queues[i];
            while (q->n && q->a[0].time < window_end) {
                Ev e = heap_pop(q);
                handle(&w, i, &e, window_end);
            }
        }
        for (int k = 0; k < w.outbox_n; k++)
            heap_push(&w.queues[w.outbox_dst[k]], w.outbox[k]);
    }

    clock_gettime(CLOCK_MONOTONIC, &t1);
    double wall = (double)(t1.tv_sec - t0.tv_sec) + (double)(t1.tv_nsec - t0.tv_nsec) / 1e9;
    int64_t sdone = 0, sstarted = 0;
    for (int i = 0; i < w.h; i++) {
        sdone += w.streams_done[i];
        sstarted += w.streams_started[i];
    }
    printf("{\"backend\": \"native-c\", \"hosts\": %d, \"sim_s\": %.6f, "
           "\"wall_s\": %.4f, \"rate\": %.6f, \"events\": %lld, "
           "\"streams_started\": %lld, \"streams_done\": %lld, "
           "\"bytes_down\": %lld, \"packets_sent\": %lld, "
           "\"packets_dropped\": %lld, \"codel_dropped\": %lld, "
           "\"retransmits\": %lld, \"resets\": %lld, "
           "\"bytes_sent\": %lld, \"bytes_recv\": %lld}\n",
           w.h, (double)end_ns / 1e9, wall, (double)end_ns / 1e9 / wall,
           (long long)w.events_handled, (long long)sstarted,
           (long long)sdone, (long long)w.bytes_down,
           (long long)w.packets_sent, (long long)w.packets_dropped,
           (long long)w.codel_dropped, (long long)w.retransmits,
           (long long)w.resets, (long long)w.bytes_sent,
           (long long)w.bytes_recv);
    return 0;
}

"""Sim-side screen proxy: batches GUI state into network streams
(parity: bluesky/simulation/qtgl/screenio.py:11-263).

Echo text is routed back to the client that issued the command; SIMINFO
(achieved sim rate, 1 Hz) and ACDATA (aircraft state subset, 5 Hz) are
published as streams.  Device -> host transfer of the ACDATA arrays happens
exactly once per tick via ``np.asarray`` on the padded state, sliced by the
active mask — the only regular host readback in the whole system.
"""
import time

import numpy as np

ACDATA_DT = 0.2       # 5 Hz (screenio.py:18-21)
SIMINFO_DT = 1.0      # 1 Hz


from .sim import DisplayState


class ScreenIO(DisplayState):
    """Duck-types simulation.sim.Screen; streams instead of buffering.

    Inherits the DisplayState surface (pan/zoom/feature/objappend/...)
    so every display stack command works in node mode too."""

    def __init__(self, sim, node):
        self.sim = sim
        self.node = node
        self.current_sender = ""      # set by the stack before echo calls
        self.echobuf = []             # bounded echo history
        self._init_display()
        self._nconf_prev = 0
        self._nconf_tot = 0
        self._nlos_prev = 0
        self._nlos_tot = 0
        self.samplecount = 0
        self.prevcount = 0
        self.prevtime = time.perf_counter()
        self.prevsimt = 0.0
        # Stream cadence is tracked locally, NOT via the process-global
        # Timer registry: with several nodes in one process a global timer
        # would fire this node's ZMQ sends from another node's thread
        # (pyzmq sockets are not thread-safe).  update() runs on this
        # node's own thread each loop iteration.
        now = time.perf_counter()
        self._next_siminfo = now + SIMINFO_DT
        self._next_acdata = now + ACDATA_DT

    def close(self):
        pass

    # ------------------------------------------------------------- commands
    def reset(self):
        """Sim RESET: clear display state + cumulative counters."""
        self._init_display()
        self._nconf_prev = self._nconf_tot = 0
        self._nlos_prev = self._nlos_tot = 0

    def objappend(self, objtype, objname, data):
        """Shape registry + broadcast to GUI clients (the reference
        mirrors shapes through events, guiclient nodeData.update)."""
        super().objappend(objtype, objname, data)
        # Wire format is the REFERENCE client's kwargs: nodeData
        # .update_poly_data(name, shape, coordinates) — guiclient.py:158
        # splats the event dict, so key names are API (coordinates=None
        # deletes the shape).
        self.node.send_event(b"SHAPE", {
            "name": objname, "shape": objtype,
            "coordinates": list(data) if data is not None else None},
            [b"*"])
        return True

    # Display-flag mirrors (reference screenio.py:132-160): the Qt
    # client's nodeData.setflag(**data) consumes these kwargs verbatim.
    def symbol(self):
        super().symbol()
        self.node.send_event(b"DISPLAYFLAG", {"flag": "SYM"}, [b"*"])
        return True

    def feature(self, sw, arg=None):
        super().feature(sw, arg)
        self.node.send_event(b"DISPLAYFLAG",
                             {"flag": sw, "args": arg}, [b"*"])
        return True

    def shownd(self, acid=None):
        """ND selection, mirrored to clients (the reference toggles the
        client-side ND via the SHOWND display event, screenio.py:132)."""
        super().shownd(acid)
        self.node.send_event(b"DISPLAYFLAG",
                             {"flag": "SHOWND", "args": acid}, [b"*"])
        return True

    def show_ssd(self, *args):
        """SSD disc selection, mirrored to clients the reference way
        (stack.py:697-700 feature('SSD', args) -> guiclient.py:270
        show_ssd)."""
        super().show_ssd(*args)
        self.node.send_event(b"DISPLAYFLAG",
                             {"flag": "SSD", "args": list(args)}, [b"*"])
        return True

    def filteralt(self, flag, bottom=None, top=None):
        super().filteralt(flag, bottom, top)
        self.node.send_event(
            b"DISPLAYFLAG",
            {"flag": "FILTERALT",
             "args": (flag, bottom, top) if flag else (False,)}, [b"*"])
        return True

    def addnavwpt(self, name, lat, lon):
        """Custom-waypoint mirror (reference screenio.py:147-150): key
        names are the reference nodeData.defwpt kwargs."""
        super().addnavwpt(name, lat, lon)
        self.node.send_event(b"DEFWPT", {"name": name, "lat": float(lat),
                                         "lon": float(lon)}, [b"*"])
        return True

    def echo(self, text="", flags=0):
        self.echobuf.append(text)
        if len(self.echobuf) > 1000:      # bounded history
            del self.echobuf[:-500]
        # ZMQ senders are comma-joined hex reply routes (multi-hop for
        # chained servers, see simnode STACKCMD); non-hex senders (the
        # TCP/telnet bridge uses 'tcpN') get their reply from the
        # bridge's own echobuf capture, so the event broadcasts instead.
        try:
            route = [bytes.fromhex(p)
                     for p in self.current_sender.split(",")] \
                if self.current_sender else None
        except ValueError:
            route = None
        self.node.send_event(b"ECHO", {"text": text, "flags": flags}, route)
        return True

    def update(self):
        self.samplecount += 1
        now = time.perf_counter()
        if now >= self._next_siminfo:
            self._next_siminfo = now + SIMINFO_DT
            self.send_siminfo()
        if now >= self._next_acdata:
            self._next_acdata = now + ACDATA_DT
            # a span of its own: after a stack command the frame is
            # built from the live state, which waits for the chunk in
            # flight (and pulls every field of it)
            with self.sim.timed("acdata_frame", "sim_frame_ms",
                                cat="node"):
                self.send_aircraft_data()
                if self.route_acid:
                    self.send_route_data()

    # -------------------------------------------------------------- streams
    def send_siminfo(self):
        """Achieved sim speed etc at 1 Hz (screenio.py:185-192).

        Uses the planned clock: with a chunk in flight (pipelined
        stepping) a device read here would stall this node thread until
        the chunk drains."""
        now = time.perf_counter()
        simt = self.sim.simt_planned
        dt = max(now - self.prevtime, 1e-9)
        speed = (simt - self.prevsimt) / dt
        self.prevtime, self.prevsimt = now, simt
        self.node.send_stream(b"SIMINFO", {
            "speed": speed, "simdt": self.sim.simdt,
            "simt": self.sim.sent(simt),
            "ntraf": self.sim.traf.ntraf, "state": self.sim.state_flag,
            "scenname": getattr(self.sim.stack, "scenname", "")})

    def send_aircraft_data(self):
        """ACDATA stream at 5 Hz, shaped to what the reference Qt
        GuiClient consumes (screenio.py:194-239 producer,
        guiclient.py:93-296 consumer): per-aircraft state arrays,
        conflict flags/counters, ASAS resolution vectors and speed caps,
        and delta-encoded trail segments.

        Counter semantics divergence: the reference counts its host-side
        unique/cumulative pair SETS; here the current counts come from
        the device scalars (directional, halved) and the totals from a
        host accumulator of count increases — same monotonic meaning
        without an [N,N] transfer at 5 Hz.
        """
        sim = self.sim
        traf = sim.traf
        edge = sim._last_edge
        if edge is not None:
            # Fused edge telemetry: every per-aircraft field below comes
            # from the most recent retired chunk edge's pack — ONE bulk
            # device->host copy (cached on the edge), no per-field pulls
            # and no stall on an in-flight pipelined chunk.  Commands
            # that mutate state invalidate the cache (stack.py), falling
            # back to the live-state path until the next edge retires.
            idx, data = edge.acdata_arrays()
            data["simt"] = sim.sent(edge.simt)
            data["id"] = [traf.ids[i] for i in idx]
            data["actype"] = [traf.types[i] for i in idx]
            nconf = int(np.asarray(edge.nconf_cur)) // 2   # -> pairs
            nlos = int(np.asarray(edge.nlos_cur)) // 2
        else:
            state = traf.state
            st = state.ac
            active = np.asarray(st.active)
            idx = np.flatnonzero(active)
            data = {"simt": sim.sent(sim.simt),
                    "id": [traf.ids[i] for i in idx],
                    "actype": [traf.types[i] for i in idx]}
            for name in ("lat", "lon", "alt", "trk", "tas", "gs", "cas",
                         "vs"):
                data[name] = np.asarray(getattr(st, name))[idx]
            asas = state.asas
            data["inconf"] = np.asarray(asas.inconf)[idx]
            data["tcpamax"] = np.asarray(asas.tcpamax)[idx]
            data["asasn"] = np.asarray(asas.asasn)[idx]
            data["asase"] = np.asarray(asas.asase)[idx]
            nconf = int(asas.nconf_cur) // 2      # directional -> pairs
            nlos = int(asas.nlos_cur) // 2
        self._nconf_tot += max(0, nconf - self._nconf_prev)
        self._nlos_tot += max(0, nlos - self._nlos_prev)
        self._nconf_prev, self._nlos_prev = nconf, nlos
        data["nconf_cur"] = nconf
        data["nconf_tot"] = self._nconf_tot
        data["nlos_cur"] = nlos
        data["nlos_tot"] = self._nlos_tot
        data["vmin"] = sim.cfg.asas.vmin
        data["vmax"] = sim.cfg.asas.vmax
        # ASAS conflict geometry, so networked clients draw their SSD
        # discs with the server's ACTUAL ZONER/DTLOOK instead of the
        # defaults (the reference client hard-codes display constants —
        # a silent divergence this stream field closes)
        data["asasrpz"] = sim.cfg.asas.rpz_m
        data["asasdtlook"] = sim.cfg.asas.dtlookahead
        # Trails: only the segments added since the last send
        # (screenio.py:216-227)
        trails = traf.trails
        data["swtrails"] = trails.active
        data["traillat0"] = trails.newlat0
        data["traillon0"] = trails.newlon0
        data["traillat1"] = trails.newlat1
        data["traillon1"] = trails.newlon1
        trails.clearnew()
        data["traillastlat"] = trails.lastlat[idx]
        data["traillastlon"] = trails.lastlon[idx]
        data["translvl"] = getattr(traf, "translvl", 0.0)
        self.node.send_stream(b"ACDATA", data)

    def send_route_data(self, acid=""):
        """ROUTEDATA for the requested aircraft (screenio.py:241-263)."""
        traf = self.sim.traf
        acid = acid or self.route_acid
        if not acid:
            return
        i = traf.id2idx(acid)
        if i < 0:
            # Aircraft gone: acid-only frame clears the GUI's route
            # display (reference sends data with just 'acid' when idx<0)
            self.node.send_stream(b"ROUTEDATA", {"acid": acid})
            self.route_acid = ""
            return
        rte = self.sim.routes.route(i)
        # pulled whole and indexed on the host: ``st.lat[i]`` is two
        # eager programs a field, compiled whenever the first frame
        # after a POS happens to fall (inside a measured window, once
        # a farm piece got shorter than the warm-up's frames: PR 36)
        st = traf.state.ac
        self.node.send_stream(b"ROUTEDATA", {
            "acid": acid,
            "aclat": float(np.asarray(st.lat)[i]),
            "aclon": float(np.asarray(st.lon)[i]),
            "wplat": list(rte.lat), "wplon": list(rte.lon),
            "wpalt": list(rte.alt), "wpspd": list(rte.spd),
            "wpname": list(rte.name), "iactwp": rte.iactwp})

